"""Norm-based on-the-fly filtering (ISSUE 5, repro.sparsity): block
norms + their pytree round-trip, the eps=0 bit-identity battery across
algorithms x meshes x fills, retained-triple monotonicity in eps, the
norm-product bound's safety (never drops a significant contribution),
the norm-predicted trivial-plan short-circuit, the configurable stack
executor bin cap, and the McWeeny purification workload's decaying
occupancy trace."""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import run_subprocess_devices
from repro.core import dbcsr, engine
from repro.core.blocking import BlockLayout, GridSpec
from repro.core.cannon import cannon_step_map
from repro.core.densify import blocked_local_matmul
from repro.core.stacks import build_stacks
from repro.core.steps import masks_empty as _masks_empty, union_view
from repro.core.summa import summa_step_map
from repro.launch.mesh import make_mesh
from repro.sparsity.filter import (count_retained_triples, product_mask,
                                   retained_pair_presence)
from repro.sparsity.norms import compute_block_norms, product_norm_bound


def _expand(mask, bs):
    return np.repeat(np.repeat(mask, bs, 0), bs, 1)


def _masked_norms(arr, mask, bs):
    norms = compute_block_norms(arr, bs, bs)
    return np.where(mask, norms, np.float32(0.0))


# ---------------------------------------------------------------------------
# norms: values, the product bound, pytree round-trips (satellite)
# ---------------------------------------------------------------------------


def test_block_norms_match_reference(rng):
    bs, nb = 8, 5
    A = rng.randn(nb * bs, nb * bs).astype(np.float32)
    norms = compute_block_norms(A, bs, bs)
    ref = np.array([[np.linalg.norm(A[i * bs:(i + 1) * bs,
                                      j * bs:(j + 1) * bs])
                     for j in range(nb)] for i in range(nb)])
    np.testing.assert_allclose(norms, ref, rtol=1e-5)
    assert norms.dtype == np.float32


def test_product_norm_bound_holds(rng):
    """||C_ij||_F <= sum_k ||A_ik|| * ||B_kj|| — the bound that makes
    the post-multiply mask predictable before executing."""
    bs, nb = 8, 4
    A = rng.randn(nb * bs, nb * bs).astype(np.float32)
    B = rng.randn(nb * bs, nb * bs).astype(np.float32)
    bound = product_norm_bound(compute_block_norms(A, bs, bs),
                               compute_block_norms(B, bs, bs))
    C = A @ B
    actual = np.array([[np.linalg.norm(C[i * bs:(i + 1) * bs,
                                         j * bs:(j + 1) * bs])
                        for j in range(nb)] for i in range(nb)])
    assert (actual <= bound * (1 + 1e-5)).all()


def test_block_norms_survive_pytree_roundtrip(rng):
    """Satellite: block_norms rebuilt through tree_unflatten aux data —
    the same mechanism PR 2 used for block_mask."""
    mesh = make_mesh((1, 1), ("data", "model"))
    grid = GridSpec("data", "model")
    A = rng.randn(128, 128).astype(np.float32)
    mask = np.zeros((4, 4), bool)
    mask[0, :] = mask[:, 0] = True
    Am = dbcsr.create(A, mesh=mesh, grid=grid, block_size=32,
                      block_mask=mask, compute_norms=True)
    assert Am.block_norms is not None
    # mask-absent blocks report norm 0
    assert (Am.block_norms[~mask] == 0).all()
    assert (Am.block_norms[mask] > 0).all()

    @jax.jit
    def scale(m: dbcsr.DBCSRMatrix) -> dbcsr.DBCSRMatrix:
        return m.scale(2.0)

    out = scale(Am)
    assert out.block_norms is not None
    # alpha=2.0 is concrete even under jit: norms rescale exactly and
    # the updated cache survives the output pytree
    np.testing.assert_allclose(out.block_norms, 2.0 * Am.block_norms,
                               rtol=1e-6)
    # explicit flatten/unflatten round-trip
    leaves, treedef = jax.tree_util.tree_flatten(Am)
    back = jax.tree_util.tree_unflatten(treedef, leaves)
    np.testing.assert_array_equal(back.block_norms, Am.block_norms)
    np.testing.assert_array_equal(back.block_mask, mask)
    # norm-free matrices still round-trip with norms None
    Bm = dbcsr.create(A, mesh=mesh, grid=grid, block_size=32)
    assert scale(Bm).block_norms is None
    # concrete-scalar scale rescales the cached norms exactly
    np.testing.assert_allclose(Am.scale(-3.0).block_norms,
                               3.0 * Am.block_norms, rtol=1e-6)


def test_norms_lazy_cache_and_filter(rng):
    mesh = make_mesh((1, 1), ("data", "model"))
    grid = GridSpec("data", "model")
    A = rng.randn(64, 64).astype(np.float32)
    Am = dbcsr.create(A, mesh=mesh, grid=grid, block_size=16)
    assert Am.block_norms is None
    n1 = Am.norms()
    assert Am.block_norms is n1  # cached
    # filter(): drops every block below eps, zeroes payload, never
    # resurrects absent blocks
    eps = float(np.median(n1))
    F = Am.filter(eps)
    np.testing.assert_array_equal(F.block_mask, n1 >= eps)
    data = np.asarray(F.data)
    for i in range(4):
        for j in range(4):
            blk = data[i * 16:(i + 1) * 16, j * 16:(j + 1) * 16]
            assert (blk == 0).all() == (not F.block_mask[i, j])
    # filtering at a higher eps only shrinks the mask
    F2 = F.filter(eps * 2)
    assert (F2.block_mask <= F.block_mask).all()


# ---------------------------------------------------------------------------
# stack generation under eps (bit-identity, monotonicity, safety)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fill", [1.0, 0.5, 0.05])
def test_eps0_stacks_bit_identical(fill, rng):
    """filter_eps=0.0 must reproduce the mask-only enumeration exactly
    (same stacks, same triples) — the acceptance bit-identity contract
    at the Generation layer."""
    bs, nb = 8, 6
    la = BlockLayout(nb * bs, nb * bs, bs, bs)
    mask_rng = np.random.RandomState(int(fill * 100))
    am = bm = None
    if fill < 1.0:
        am = mask_rng.rand(nb, nb) < fill
        bm = mask_rng.rand(nb, nb) < fill
    A = rng.randn(nb * bs, nb * bs).astype(np.float32)
    B = rng.randn(nb * bs, nb * bs).astype(np.float32)
    an = compute_block_norms(A, bs, bs)
    bn = compute_block_norms(B, bs, bs)
    if am is not None:
        an, bn = np.where(am, an, 0), np.where(bm, bn, 0)
    base = build_stacks(la, la, 13, a_mask=am, b_mask=bm)
    filt = build_stacks(la, la, 13, a_mask=am, b_mask=bm,
                        a_norms=an, b_norms=bn, filter_eps=0.0)
    assert len(base) == len(filt)
    for p, q in zip(base, filt):
        np.testing.assert_array_equal(p.triples, q.triples)


def test_retained_triples_monotone_in_eps(rng):
    """Satellite property: retained triples non-increasing in eps, with
    the executor stats accounting for every dropped triple."""
    bs, nb = 8, 6
    m = nb * bs
    mask_rng = np.random.RandomState(3)
    am = mask_rng.rand(nb, nb) < 0.6
    bm = mask_rng.rand(nb, nb) < 0.6
    A = rng.randn(m, m).astype(np.float32)
    B = rng.randn(m, m).astype(np.float32)
    an, bn = _masked_norms(A, am, bs), _masked_norms(B, bm, bs)
    mask_triples = int((am.astype(np.int64) @ bm.astype(np.int64)).sum())
    prev = None
    for eps in [0.0, 1.0, 20.0, 50.0, 70.0, 100.0, 1e9]:
        plan = engine.build_executor_plan(
            m, m, m, bs, bs, bs, 64, a_mask=am, b_mask=bm,
            a_norms=an, b_norms=bn, filter_eps=eps)
        # count_retained_triples (the planner's occupancy numerator)
        # agrees with the plan the executor actually dispatches
        assert plan.n_entries == count_retained_triples(am, bm, an, bn, eps)
        assert plan.n_unfiltered_entries == mask_triples
        stats = plan.stats()
        assert stats["n_norm_filtered_triples"] == \
            mask_triples - plan.n_entries
        if prev is not None:
            assert plan.n_entries <= prev
        prev = plan.n_entries
    assert prev == 0  # eps=1e9 empties the product


def test_norm_bound_never_drops_significant_block(rng):
    """Safety: a triple whose TRUE contribution norm ||A_ik @ B_kj||_F
    is >= eps always survives the filter (submultiplicativity makes
    the product bound an over-approximation, never an under one)."""
    bs, nb = 8, 5
    m = nb * bs
    A = rng.randn(m, m).astype(np.float32)
    B = rng.randn(m, m).astype(np.float32)
    an = compute_block_norms(A, bs, bs)
    bn = compute_block_norms(B, bs, bs)
    for eps in [10.0, 50.0, 80.0]:
        plan = engine.build_executor_plan(
            m, m, m, bs, bs, bs, 64, a_norms=an, b_norms=bn,
            filter_eps=eps)
        retained = {tuple(t) for p in plan.plans for t in p.triples.tolist()}
        for i in range(nb):
            for k in range(nb):
                for j in range(nb):
                    true = np.linalg.norm(
                        A[i * bs:(i + 1) * bs, k * bs:(k + 1) * bs]
                        @ B[k * bs:(k + 1) * bs, j * bs:(j + 1) * bs])
                    if true >= eps:
                        assert (i * nb + k, k * nb + j, i * nb + j) \
                            in retained, (i, k, j, eps)


def test_filtered_executor_matches_dropped_triple_reference(rng):
    """The filtered executor computes exactly the sum of retained
    contributions (not an approximation of it)."""
    bs, nb = 8, 5
    m = nb * bs
    mask_rng = np.random.RandomState(7)
    am = mask_rng.rand(nb, nb) < 0.7
    bm = mask_rng.rand(nb, nb) < 0.7
    A = rng.randn(m, m).astype(np.float32) * _expand(am, bs)
    B = rng.randn(m, m).astype(np.float32) * _expand(bm, bs)
    an, bn = _masked_norms(A, am, bs), _masked_norms(B, bm, bs)
    eps = 60.0
    f = blocked_local_matmul(m, m, m, block_m=bs, block_k=bs, block_n=bs,
                             kernel="ref", a_mask=am, b_mask=bm,
                             a_norms=an, b_norms=bn, filter_eps=eps)
    plan = f.executor_plan
    assert 0 < plan.n_entries < plan.n_unfiltered_entries  # partial drop
    C = np.asarray(f(jnp.asarray(A), jnp.asarray(B)))
    keep = retained_pair_presence(am, bm, an, bn, eps)
    ref = np.zeros((m, m), np.float32)
    for i in range(nb):
        for k in range(nb):
            for j in range(nb):
                if keep[i, k, j]:
                    ref[i * bs:(i + 1) * bs, j * bs:(j + 1) * bs] += \
                        A[i * bs:(i + 1) * bs, k * bs:(k + 1) * bs] \
                        @ B[k * bs:(k + 1) * bs, j * bs:(j + 1) * bs]
    np.testing.assert_allclose(C, ref, rtol=0, atol=1e-4)


# ---------------------------------------------------------------------------
# the union view's norms: SPMD union-of-max semantics
# ---------------------------------------------------------------------------


def _union_norms(smap, an, bn):
    """The union view's norms per step, with every block present."""
    am = np.ones(an.shape, bool)
    bm = np.ones(bn.shape, bool)
    return [s["pair_norms"] if "pair_norms" in s
            else (s["a_norms"], s["b_norms"])
            for s in union_view(smap, am, bm, an, bn)]


def test_cannon_union_view_norms_union_of_max(rng):
    """Brute force: at each step the built tensor is the max over all
    (i, j) ranks of that rank's chunk norm products — so eps drops a
    triple only when it is sub-eps on EVERY rank."""
    pg, lb = 2, 3
    nb = pg * lb
    an = np.abs(rng.randn(nb, nb)).astype(np.float32)
    bn = np.abs(rng.randn(nb, nb)).astype(np.float32)
    steps = _union_norms(cannon_step_map(nb, nb, nb, pg), an, bn)
    assert len(steps) == pg
    for t, built in enumerate(steps):
        ref = np.zeros((lb, lb, lb))
        for i in range(pg):
            for j in range(pg):
                q = (i + j + t) % pg
                ac = an[i * lb:(i + 1) * lb, q * lb:(q + 1) * lb]
                bc = bn[q * lb:(q + 1) * lb, j * lb:(j + 1) * lb]
                ref = np.maximum(ref, ac[:, :, None] * bc[None, :, :])
        np.testing.assert_allclose(built, ref, rtol=1e-6)


def test_summa_union_view_norms_factored_max(rng):
    pr = pc = 2
    nb = 4
    an = np.abs(rng.randn(nb, nb)).astype(np.float32)
    bn = np.abs(rng.randn(nb, nb)).astype(np.float32)
    panels = _union_norms(summa_step_map(nb, nb, nb, pr, pc, 2), an, bn)
    assert len(panels) == 2
    for p, (ua, ub) in enumerate(panels):
        ksl = slice(p * 2, (p + 1) * 2)
        np.testing.assert_allclose(
            ua, np.maximum(an[:2, ksl], an[2:, ksl]), rtol=1e-6)
        np.testing.assert_allclose(
            ub, np.maximum(bn[ksl, :2], bn[ksl, 2:]), rtol=1e-6)


def test_masks_empty_fires_on_norm_filtered_steps():
    """Satellite (planner bugfix): eps filtering can empty a step (or a
    whole product) whose binary masks are non-empty — _masks_empty must
    see it so the trivial-plan short-circuit / step skipping fires."""
    am = np.ones((4, 4), bool)
    an = np.full((4, 4), 1e-4, np.float32)
    pair = am[:, :, None] & am[None, :, :]
    pn = (an[:, :, None] * an[None, :, :]).astype(np.float32)
    # mask-non-empty, all norm products 1e-8 < eps=1e-6 -> empty
    assert not _masks_empty({"pair_mask": pair})
    assert _masks_empty({"pair_mask": pair, "pair_norms": pn,
                         "filter_eps": 1e-6})
    # eps=0 never empties anything
    assert not _masks_empty({"pair_mask": pair, "pair_norms": pn,
                             "filter_eps": 0.0})
    # factored form
    assert _masks_empty({"a_mask": am, "b_mask": am, "a_norms": an,
                         "b_norms": an, "filter_eps": 1e-6})
    assert not _masks_empty({"a_mask": am, "b_mask": am, "a_norms": an,
                             "b_norms": an, "filter_eps": 1e-9})


def test_trivial_plan_on_norm_predicted_empty(rng):
    """A product whose binary masks are non-empty but whose every norm
    product is below eps short-circuits to the planner's trivial plan
    and executes as exact zeros."""
    mesh = make_mesh((1, 1), ("data", "model"))
    grid = GridSpec("data", "model")
    A = (rng.randn(64, 64) * 1e-5).astype(np.float32)
    B = (rng.randn(64, 64) * 1e-5).astype(np.float32)
    Am = dbcsr.create(A, mesh=mesh, grid=grid, block_size=16)
    Bm = dbcsr.create(B, mesh=mesh, grid=grid, block_size=16)
    C, plan = dbcsr.multiply(Am, Bm, mesh=mesh, filter_eps=1e-6,
                             return_plan=True)
    assert plan.trivial and plan.occupancy == 0.0
    assert (np.asarray(C.data) == 0).all()
    assert C.block_mask is not None and not C.block_mask.any()
    # the same operands multiply normally without the filter
    C2, plan2 = dbcsr.multiply(Am, Bm, mesh=mesh, return_plan=True)
    assert not plan2.trivial
    np.testing.assert_allclose(np.asarray(C2.data), A @ B,
                               rtol=0, atol=1e-6)


def test_product_mask_is_retained_support(rng):
    bs, nb = 8, 6
    m = nb * bs
    mask_rng = np.random.RandomState(5)
    am = mask_rng.rand(nb, nb) < 0.5
    bm = mask_rng.rand(nb, nb) < 0.5
    A = rng.randn(m, m).astype(np.float32) * _expand(am, bs)
    B = rng.randn(m, m).astype(np.float32) * _expand(bm, bs)
    an, bn = _masked_norms(A, am, bs), _masked_norms(B, bm, bs)
    for eps in [None, 0.0, 40.0, 1e9]:
        pm = product_mask(am, bm, an, bn, eps)
        keep = retained_pair_presence(am, bm, an, bn, eps)
        np.testing.assert_array_equal(pm, keep.any(axis=1))
    # eps None / 0.0 reduce to the symbolic mask product
    np.testing.assert_array_equal(
        product_mask(am, bm, an, bn, 0.0),
        (am.astype(np.int64) @ bm.astype(np.int64)) > 0)


# ---------------------------------------------------------------------------
# configurable stack-executor bin cap (satellite)
# ---------------------------------------------------------------------------


def _ragged_masks():
    mask_rng = np.random.RandomState(11)
    am = mask_rng.rand(16, 16) < 0.12
    bm = mask_rng.rand(16, 16) < 0.12
    am[0, :] = True  # one dense row -> wildly ragged run lengths
    return am, bm


def test_stack_bins_kwarg_and_env(monkeypatch):
    am, bm = _ragged_masks()
    m = 16 * 8
    kw = dict(a_mask=am, b_mask=bm)
    default = engine.build_executor_plan(m, m, m, 8, 8, 8, 64, **kw)
    assert 1 < default.n_bins <= 4
    single = engine.build_executor_plan(m, m, m, 8, 8, 8, 64, **kw,
                                        stack_bins=1)
    assert single.n_bins == 1
    # bins only refine: same entries, padding never worse than unbinned
    assert single.n_entries == default.n_entries
    assert default.n_padding <= single.n_padding
    wide = engine.build_executor_plan(m, m, m, 8, 8, 8, 64, **kw,
                                      stack_bins=8)
    assert default.n_bins <= wide.n_bins <= 8
    assert wide.n_padding <= default.n_padding
    # the env knob reaches the same resolution path
    monkeypatch.setenv("DBCSR_STACK_BINS", "1")
    assert engine.resolve_stack_bins() == 1
    env_plan = engine.build_executor_plan(m, m, m, 8, 8, 8, 64, **kw)
    assert env_plan.n_bins == 1
    monkeypatch.delenv("DBCSR_STACK_BINS")
    assert engine.resolve_stack_bins() == 4
    with pytest.raises(ValueError):
        engine.resolve_stack_bins(0)


def test_stack_bins_distinct_memo_entries():
    """stack_bins participates in the plan memo key — a bin-cap sweep
    must not serve one cap's layout for another."""
    am, bm = _ragged_masks()
    m = 16 * 8
    p1 = engine.build_executor_plan(m, m, m, 8, 8, 8, 64,
                                    a_mask=am, b_mask=bm, stack_bins=1)
    p4 = engine.build_executor_plan(m, m, m, 8, 8, 8, 64,
                                    a_mask=am, b_mask=bm, stack_bins=4)
    assert p1 is not p4 and p1.n_bins != p4.n_bins


# ---------------------------------------------------------------------------
# eps=0 bit-identity battery: algorithms x meshes x fills (acceptance)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("algo", ["cannon", "summa", "ts_k"])
@pytest.mark.parametrize("fill", [1.0, 0.5, 0.05])
def test_eps0_bit_identity_1x1(algo, fill, rng):
    from repro.core.multiply import distributed_matmul

    mesh = make_mesh((1, 1), ("data", "model"))
    grid = GridSpec("data", "model")
    bs, nb = 8, 6
    m = nb * bs
    am = bm = None
    if fill < 1.0:
        mask_rng = np.random.RandomState(int(fill * 100))
        am = mask_rng.rand(nb, nb) < fill
        bm = mask_rng.rand(nb, nb) < fill
        am[0, 0] = bm[0, 0] = True
    A = rng.randn(m, m).astype(np.float32)
    B = rng.randn(m, m).astype(np.float32)
    if am is not None:
        A, B = A * _expand(am, bs), B * _expand(bm, bs)
    kw = dict(mesh=mesh, grid=grid, algorithm=algo, densify=False,
              block_m=bs, block_k=bs, block_n=bs, local_kernel="ref",
              a_mask=am, b_mask=bm)
    C0 = distributed_matmul(jnp.asarray(A), jnp.asarray(B), **kw)
    C1 = distributed_matmul(jnp.asarray(A), jnp.asarray(B), **kw,
                            filter_eps=0.0)
    assert np.array_equal(np.asarray(C0), np.asarray(C1)), \
        f"{algo}@{fill}: eps=0 not bit-identical"


FILTER_BATTERY = r"""
import json
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compat import make_mesh
from repro.core.blocking import GridSpec
from repro.core.multiply import distributed_matmul

rng = np.random.RandomState(0)
out = {}
bs = 8
grid = GridSpec("data", "model")
mesh = make_mesh((2, 2), ("data", "model"))
sh = NamedSharding(mesh, P("data", "model"))
mesh3 = make_mesh((2, 2, 2), ("pod", "data", "model"))
grid3 = GridSpec("data", "model", stack_axis="pod")
sh3 = NamedSharding(mesh3, P("data", "model"))
expand = lambda m: np.repeat(np.repeat(m, bs, 0), bs, 1)

M = K = N = 64
nb = M // bs
for fill in (1.0, 0.5, 0.05):
    am = bm = None
    A = rng.randn(M, K).astype(np.float32)
    B = rng.randn(K, N).astype(np.float32)
    if fill < 1.0:
        am = rng.rand(nb, nb) < fill
        bm = rng.rand(nb, nb) < fill
        am[0, 0] = bm[0, 0] = True
        A *= expand(am); B *= expand(bm)
    cases = [("cannon", mesh, grid, sh, {}),
             ("summa", mesh, grid, sh, {}),
             ("summa_gather", mesh, grid, sh, {"bcast": "gather"}),
             ("ts_k", mesh, grid, sh, {}),
             ("cannon25d", mesh3, grid3, sh3, {})]
    for name, msh, grd, shd, extra in cases:
        algo = "summa" if name.startswith("summa") else name
        Ad, Bd = jax.device_put(A, shd), jax.device_put(B, shd)
        kw = dict(mesh=msh, grid=grd, algorithm=algo, densify=False,
                  block_m=bs, block_k=bs, block_n=bs, local_kernel="ref",
                  a_mask=am, b_mask=bm, **extra)
        C0 = np.asarray(distributed_matmul(Ad, Bd, **kw))
        C1 = np.asarray(distributed_matmul(Ad, Bd, **kw, filter_eps=0.0))
        out[f"{name}@{fill}_bitwise"] = bool(np.array_equal(C0, C1))
        # eps > 0: dropped contributions bounded by nbk * eps per block
        eps = 10.0
        C2 = np.asarray(distributed_matmul(Ad, Bd, **kw, filter_eps=eps))
        err = float(np.max(np.abs(C2 - A @ B)))
        out[f"{name}@{fill}_eps_err"] = err
        out[f"{name}@{fill}_eps_ok"] = bool(err <= nb * eps + 1e-3)
print("JSON" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def filter_battery():
    stdout = run_subprocess_devices(FILTER_BATTERY, n_devices=8, timeout=900)
    line = [l for l in stdout.splitlines() if l.startswith("JSON")][-1]
    return json.loads(line[4:])


@pytest.mark.parametrize("algo", ["cannon", "summa", "summa_gather",
                                  "ts_k", "cannon25d"])
@pytest.mark.parametrize("fill", [1.0, 0.5, 0.05])
def test_eps0_bit_identity_battery(filter_battery, algo, fill):
    assert filter_battery[f"{algo}@{fill}_bitwise"], \
        (algo, fill, "filter_eps=0.0 changed bits")


@pytest.mark.parametrize("algo", ["cannon", "summa", "summa_gather",
                                  "ts_k", "cannon25d"])
@pytest.mark.parametrize("fill", [1.0, 0.5, 0.05])
def test_eps_error_bounded_battery(filter_battery, algo, fill):
    assert filter_battery[f"{algo}@{fill}_eps_ok"], \
        (algo, fill, filter_battery[f"{algo}@{fill}_eps_err"])


# ---------------------------------------------------------------------------
# purification workload (single device keeps it fast; the 4-device run
# is examples/purification.py)
# ---------------------------------------------------------------------------


def test_mcweeny_purification_occupancy_decays():
    from repro.sparsity.workloads import (banded_hamiltonian,
                                          initial_density, mcweeny_purify)

    n, bs = 128, 16
    H, mask = banded_hamiltonian(n, bs, half_bandwidth=3)
    mesh = make_mesh((1, 1), ("data", "model"))
    grid = GridSpec("data", "model")
    P0 = dbcsr.create(initial_density(H).astype(np.float32), mesh=mesh,
                      grid=grid, block_size=bs, block_mask=mask)
    P, trace = mcweeny_purify(
        P0, mesh=mesh, n_iter=8, filter_eps=1e-6,
        multiply_kw=dict(densify=False, local_kernel="ref"))
    occs = [t["occupancy"] for t in trace]
    peak = occs.index(max(occs))
    assert all(occs[i + 1] <= occs[i] + 1e-12
               for i in range(peak, len(occs) - 1)), occs
    assert occs[-1] < occs[0], occs  # net sparsification
    assert trace[-1]["idempotency"] < 1e-4  # converged to a projector
    assert abs(trace[-1]["trace_P"] - n // 2) < 0.5  # electrons conserved
    # the filter actually dropped work somewhere along the run
    assert any(t.get("n_norm_filtered_triples", 0) > 0 for t in trace)
