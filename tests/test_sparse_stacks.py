"""Occupancy-aware stack generation (ISSUE 2): mask filtering in the
Generation phase, ragged-run scheduling invariants, mask-fingerprint
plan memoization, occupancy-binned autotune lookup, and the sparse
distributed paths (per-shift / per-panel union plans + empty-step
skipping) against masked-densified oracles."""
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import run_subprocess_devices
from repro.core import engine
from repro.core.blocking import BlockLayout
from repro.core.densify import blocked_local_matmul
from repro.core.cannon import cannon_step_map
from repro.core.multiply import _blocked_steps_lm
from repro.core.steps import masks_empty as _masks_empty, union_view
from repro.core.summa import summa_step_map
from repro.core.stacks import build_stacks


def _cannon_pair_masks(am, bm, pg):
    smap = cannon_step_map(*am.shape, bm.shape[1], pg)
    return [s["pair_mask"] for s in union_view(smap, am, bm)]


def _summa_panel_masks(am, bm, pr, pc, n_panels):
    smap = summa_step_map(*am.shape, bm.shape[1], pr, pc, n_panels)
    return [(s["a_mask"], s["b_mask"]) for s in union_view(smap, am, bm)]


def _expand(mask, bs):
    return np.repeat(np.repeat(mask, bs, 0), bs, 1)


# ---------------------------------------------------------------------------
# Generation phase: dense bit-identity + masked filtering
# ---------------------------------------------------------------------------


def test_dense_masks_bit_identical():
    """All-true masks must reproduce the dense enumeration exactly —
    same stacks, same triples, same dtype (acceptance criterion)."""
    a = BlockLayout(64, 96, 16, 16)
    b = BlockLayout(96, 80, 16, 16)
    for stack_size in (5, 13, 30_000):
        dense = build_stacks(a, b, stack_size=stack_size)
        masked = build_stacks(
            a, b, stack_size=stack_size,
            a_mask=np.ones((4, 6), bool), b_mask=np.ones((6, 5), bool))
        assert len(dense) == len(masked)
        for p, q in zip(dense, masked):
            assert p.triples.dtype == q.triples.dtype == np.int32
            np.testing.assert_array_equal(p.triples, q.triples)


def test_masked_triple_count_is_mask_product():
    rng = np.random.RandomState(3)
    a = BlockLayout(32, 48, 8, 8)
    b = BlockLayout(48, 40, 8, 8)
    am = rng.rand(4, 6) < 0.4
    bm = rng.rand(6, 5) < 0.4
    plans = build_stacks(a, b, stack_size=7, a_mask=am, b_mask=bm)
    expected = int((am.astype(np.int64) @ bm.astype(np.int64)).sum())
    assert sum(p.size for p in plans) == expected
    # every triple's (i, k) and (k, j) are present in the masks
    for p in plans:
        i, kk = p.triples[:, 0] // 6, p.triples[:, 0] % 6
        kk2, j = p.triples[:, 1] // 5, p.triples[:, 1] % 5
        np.testing.assert_array_equal(kk, kk2)
        assert am[i, kk].all() and bm[kk, j].all()


@pytest.mark.parametrize("fill", [0.6, 0.25, 0.1])
def test_run_contiguity_on_ragged_runs(fill):
    """Scheduler invariants under ragged k-runs: within every stack each
    C block's updates form one contiguous run, and no run is split
    across stacks (each C block lives in exactly one stack)."""
    rng = np.random.RandomState(int(fill * 100))
    a = BlockLayout(64, 96, 8, 8)
    b = BlockLayout(96, 72, 8, 8)
    am = rng.rand(8, 12) < fill
    bm = rng.rand(12, 9) < fill
    plans = build_stacks(a, b, stack_size=20, a_mask=am, b_mask=bm)
    owners = {}
    for si, p in enumerate(plans):
        c = p.triples[:, 2]
        seen = set()
        prev = None
        for x in c.tolist():
            if x != prev:
                assert x not in seen, "C block revisited non-contiguously"
                seen.add(x)
                prev = x
        for x in seen:
            assert x not in owners, "C block's k-run split across stacks"
            owners[x] = si
        # stacks respect the size cap unless a single run exceeds it
        if p.size > 20:
            assert len(seen) == 1


def test_empty_rows_and_cols():
    """An empty A block-row / B block-col produce no triples for the
    corresponding C row / col."""
    a = BlockLayout(32, 32, 8, 8)
    b = BlockLayout(32, 32, 8, 8)
    am = np.ones((4, 4), bool)
    am[2, :] = False  # empty A block-row
    bm = np.ones((4, 4), bool)
    bm[:, 1] = False  # empty B block-col
    plans = build_stacks(a, b, a_mask=am, b_mask=bm)
    c_idx = np.concatenate([p.triples[:, 2] for p in plans])
    ci, cj = c_idx // 4, c_idx % 4
    assert not (ci == 2).any() and not (cj == 1).any()
    assert sum(p.size for p in plans) == 3 * 4 * 3


# ---------------------------------------------------------------------------
# executor vs masked-densified oracle (acceptance: fills + structured)
# ---------------------------------------------------------------------------


def _mask_case(name, nb, rng):
    if name == "empty_row":
        am = np.ones((nb, nb), bool)
        am[1, :] = False
        return am, np.ones((nb, nb), bool)
    if name == "empty_col":
        bm = np.ones((nb, nb), bool)
        bm[:, 2] = False
        return np.ones((nb, nb), bool), bm
    fill = float(name)
    return rng.rand(nb, nb) < fill, rng.rand(nb, nb) < fill


@pytest.mark.parametrize("kernel", ["ref", "smm"])
@pytest.mark.parametrize("case", ["1.0", "0.5", "0.1",
                                  "empty_row", "empty_col"])
def test_masked_executor_vs_densified_oracle(case, kernel, rng):
    block, nb = 8, 5
    m = k = n = block * nb
    am, bm = _mask_case(case, nb, np.random.RandomState(hash(case) % 1000))
    A = rng.randn(m, k).astype(np.float32) * _expand(am, block)
    B = rng.randn(k, n).astype(np.float32) * _expand(bm, block)

    f = blocked_local_matmul(m, k, n, block_m=block, block_k=block,
                             block_n=block, stack_size=2 * nb, kernel=kernel,
                             a_mask=am, b_mask=bm)
    C = np.asarray(f(jnp.asarray(A), jnp.asarray(B)))
    np.testing.assert_allclose(C, A @ B, rtol=0, atol=1e-4)
    # the plan dispatches exactly the present triples
    plan = f.executor_plan
    assert plan.n_entries == int((am.astype(np.int64) @ bm.astype(np.int64)).sum())
    stats = plan.stats()
    assert stats["n_dense_triples"] == nb ** 3
    assert stats["n_skipped_triples"] == nb ** 3 - plan.n_entries
    assert stats["occupancy"] == pytest.approx(plan.n_entries / nb ** 3)


def test_ten_percent_occupancy_triple_bound(rng):
    """Acceptance criterion: at 10% block occupancy the plan holds at
    most 15% of the dense triple count."""
    nb = 10
    mask_rng = np.random.RandomState(7)
    am = mask_rng.rand(nb, nb) < 0.1
    # one-sided 10% mask: triple fraction == mask fill exactly
    f = blocked_local_matmul(nb * 8, nb * 8, nb * 8, block_m=8, block_k=8,
                             block_n=8, kernel="ref", a_mask=am)
    plan = f.executor_plan
    assert plan.n_entries <= 0.15 * plan.n_dense_triples
    # two-sided 10% masks: ~1% expected, far under the bound
    bm = mask_rng.rand(nb, nb) < 0.1
    g = blocked_local_matmul(nb * 8, nb * 8, nb * 8, block_m=8, block_k=8,
                             block_n=8, kernel="ref", a_mask=am, b_mask=bm)
    assert g.executor_plan.n_entries <= 0.15 * g.executor_plan.n_dense_triples


def test_empty_product_executor_is_noop():
    block, nb = 8, 4
    m = k = n = block * nb
    f = blocked_local_matmul(m, k, n, block_m=block, block_k=block,
                             block_n=block, kernel="ref",
                             a_mask=np.zeros((nb, nb), bool))
    assert f.executor_plan.n_stacks == 0
    C = np.asarray(f(jnp.zeros((m, k), jnp.float32),
                     jnp.ones((k, n), jnp.float32)))
    assert (C == 0).all()


# ---------------------------------------------------------------------------
# plan memoization on mask content fingerprints
# ---------------------------------------------------------------------------


def test_plan_memo_hits_on_mask_content():
    rng = np.random.RandomState(11)
    mask = rng.rand(8, 8) < 0.5
    p1 = engine.build_executor_plan(64, 64, 64, 8, 8, 8, 100, a_mask=mask)
    # distinct array object, identical content -> same memoized plan
    p2 = engine.build_executor_plan(64, 64, 64, 8, 8, 8, 100,
                                    a_mask=mask.copy())
    assert p1 is p2
    # different content -> different plan
    other = mask.copy()
    other[0, 0] = not other[0, 0]
    p3 = engine.build_executor_plan(64, 64, 64, 8, 8, 8, 100, a_mask=other)
    assert p3 is not p1
    # dense plan is distinct from any masked plan
    p4 = engine.build_executor_plan(64, 64, 64, 8, 8, 8, 100)
    assert p4 is not p1


def test_plan_build_leaves_caller_mask_writable():
    """Fingerprinting copies the mask: the caller's array must stay
    writable (evolving sparsity patterns re-fingerprint per content)."""
    mask = np.ones((8, 8), bool)
    engine.build_executor_plan(64, 64, 64, 8, 8, 8, 100, a_mask=mask)
    mask[0, 0] = False  # must not raise "read-only"
    p = engine.build_executor_plan(64, 64, 64, 8, 8, 8, 100, a_mask=mask)
    assert p.n_entries == 8 * 8 * 8 - 8  # one absent A block = nbk fewer


# ---------------------------------------------------------------------------
# occupancy-binned autotune lookup
# ---------------------------------------------------------------------------


def test_fill_bin_snapping():
    from repro.kernels.smm.autotune import fill_bin
    assert fill_bin(1.0) == 1.0
    assert fill_bin(0.9) == 1.0
    assert fill_bin(0.4) == 0.5
    assert fill_bin(0.18) == 0.2
    assert fill_bin(0.04) == 0.05
    assert fill_bin(0.0001) == 0.05


def test_best_params_occupancy_binned(tmp_path):
    from repro.kernels.smm.autotune import best_params, best_params_for
    cache = tmp_path / "smm_autotune.json"
    cache.write_text(json.dumps({
        "22": {"best": {"align": True, "stack_tile": 30000}},
        "22@0.05": {"best": {"align": False, "stack_tile": 1024}},
    }))
    path = str(cache)
    # dense lookup -> legacy un-suffixed key
    assert best_params(22, path) == (True, 30000)
    # sparse lookup -> occupancy-binned winner (not the dense one)
    assert best_params(22, path, fill=0.04) == (False, 1024)
    assert best_params_for(22, 22, 22, path, fill=0.04) == (False, 1024)
    # bin with no recorded sweep falls back to the dense entry
    assert best_params(22, path, fill=0.4) == (True, 30000)


def test_stack_executor_resolves_binned_defaults(tmp_path, monkeypatch):
    """A 10%-fill workload resolves stack_size from its occupancy bin,
    not the dense winner."""
    from repro.kernels.smm import autotune
    cache = tmp_path / "smm_autotune.json"
    cache.write_text(json.dumps({
        "8": {"best": {"align": False, "stack_tile": 30000}},
        "8@0.05": {"best": {"align": False, "stack_tile": 64}},
    }))
    monkeypatch.setattr(autotune, "DEFAULT_CACHE", str(cache))
    mask_rng = np.random.RandomState(5)
    am = mask_rng.rand(10, 10) < 0.1
    bm = mask_rng.rand(10, 10) < 0.1
    f = blocked_local_matmul(80, 80, 80, block_m=8, block_k=8, block_n=8,
                             kernel="ref", a_mask=am, b_mask=bm)
    assert f.stack_size == 64
    g = blocked_local_matmul(80, 80, 80, block_m=8, block_k=8, block_n=8,
                             kernel="ref")
    assert g.stack_size == 30000


# ---------------------------------------------------------------------------
# distributed-layer mask slicing (host-side helpers, no devices needed)
# ---------------------------------------------------------------------------


def test_cannon_pair_masks_skip_steps():
    """A confined to chunk-column 0 and B to chunk-row 0 on a 2x2 grid:
    device (i, j) needs chunk q = (i+j+t) % 2 of both, so only shift
    step 0 (where some rank has q=0... for i+j even) can be non-empty
    at t making (i+j+t) % 2 == 0."""
    am = np.zeros((8, 8), bool)
    am[:, :4] = True   # A present only in chunk column q=0
    bm = np.zeros((8, 8), bool)
    bm[:4, :] = True   # B present only in chunk row q=0
    pairs = _cannon_pair_masks(am, bm, 2)
    # q=0 is reached by (i+j+t) % 2 == 0; both t=0 (i+j even) and t=1
    # (i+j odd) have ranks hitting q=0 -> both steps non-empty...
    assert [p.any() for p in pairs] == [True, True]
    # ...but confine A to the (0, 0) chunk only: product needs i=0, q=0
    # => t = (0 - 0 - 0) % 2 = 0 and j=0; step 1 is empty and skipped.
    am2 = np.zeros((8, 8), bool)
    am2[:4, :4] = True
    bm2 = np.zeros((8, 8), bool)
    bm2[:4, :4] = True  # B chunk (0, 0) only
    pairs2 = _cannon_pair_masks(am2, bm2, 2)
    assert [p.any() for p in pairs2] == [True, False]
    lm = _blocked_steps_lm(32, 32, 32, [
        [{"pair_mask": p}] for p in pairs2],
        block_m=8, block_k=8, block_n=8, stack_size=None, align=None,
        kernel="ref")
    assert lm.stepwise and lm.empty_steps == frozenset({1})


def test_summa_panel_masks_skip_panels():
    """B empty in the K range of panel 1 -> that panel is skipped."""
    am = np.ones((8, 8), bool)
    bm = np.ones((8, 8), bool)
    bm[4:, :] = False  # panel 1's K block range is empty in B
    panels = _summa_panel_masks(am, bm, 2, 2, 2)
    assert not _masks_empty({"a_mask": panels[0][0], "b_mask": panels[0][1]})
    assert _masks_empty({"a_mask": panels[1][0], "b_mask": panels[1][1]})


# ---------------------------------------------------------------------------
# distributed sparse battery (multi-device subprocess)
# ---------------------------------------------------------------------------


SPARSE_BATTERY = r"""
import json
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compat import make_mesh
from repro.core import dbcsr
from repro.core.blocking import GridSpec
from repro.core.multiply import distributed_matmul

rng = np.random.RandomState(0)
out = {}
bs = 8
M, K, N = 64, 96, 80
am = rng.rand(M // bs, K // bs) < 0.3
bm = rng.rand(K // bs, N // bs) < 0.3
expand = lambda m: np.repeat(np.repeat(m, bs, 0), bs, 1)
A = rng.randn(M, K).astype(np.float32) * expand(am)
B = rng.randn(K, N).astype(np.float32) * expand(bm)
ref = A @ B

grid = GridSpec("data", "model")
mesh = make_mesh((2, 2), ("data", "model"))
sh = NamedSharding(mesh, P("data", "model"))
Ad, Bd = jax.device_put(A, sh), jax.device_put(B, sh)
C = distributed_matmul(Ad, Bd, mesh=mesh, grid=grid, algorithm="cannon",
                       densify=False, block_m=bs, block_k=bs, block_n=bs,
                       local_kernel="ref", a_mask=am, b_mask=bm)
out["cannon_sparse_2x2"] = float(np.max(np.abs(np.asarray(C) - ref)))
for bcast in ("psum", "gather"):
    C = distributed_matmul(Ad, Bd, mesh=mesh, grid=grid, algorithm="summa",
                           densify=False, block_m=bs, block_k=bs, block_n=bs,
                           local_kernel="ref", a_mask=am, b_mask=bm,
                           bcast=bcast)
    out[f"summa_{bcast}_sparse_2x2"] = float(np.max(np.abs(np.asarray(C) - ref)))

# non-square summa with masks (per-panel plans + panel mask unions)
mesh21 = make_mesh((2, 1), ("data", "model"))
sh21 = NamedSharding(mesh21, P("data", "model"))
A21, B21 = jax.device_put(A, sh21), jax.device_put(B, sh21)
C = distributed_matmul(A21, B21, mesh=mesh21, grid=grid, algorithm="summa",
                       densify=False, block_m=bs, block_k=bs, block_n=bs,
                       local_kernel="ref", a_mask=am, b_mask=bm)
out["summa_psum_sparse_2x1"] = float(np.max(np.abs(np.asarray(C) - ref)))

# 2.5D cannon with masks (per-inner-step unions over replicas)
mesh3 = make_mesh((2, 2, 2), ("pod", "data", "model"))
grid3 = GridSpec("data", "model", stack_axis="pod")
M2 = K2 = N2 = 64
am2 = rng.rand(M2 // bs, K2 // bs) < 0.4
bm2 = rng.rand(K2 // bs, N2 // bs) < 0.4
A2 = rng.randn(M2, K2).astype(np.float32) * expand(am2)
B2 = rng.randn(K2, N2).astype(np.float32) * expand(bm2)
sh3 = NamedSharding(mesh3, P("data", "model"))
A2d, B2d = jax.device_put(A2, sh3), jax.device_put(B2, sh3)
C = distributed_matmul(A2d, B2d, mesh=mesh3, grid=grid3,
                       algorithm="cannon25d", densify=False, block_m=bs,
                       block_k=bs, block_n=bs, local_kernel="ref",
                       a_mask=am2, b_mask=bm2)
out["cannon25d_sparse"] = float(np.max(np.abs(np.asarray(C) - A2 @ B2)))

# dbcsr API end-to-end: blocked sparse multiply + symbolic result mask
Am = dbcsr.create(A, mesh=mesh, grid=grid, block_size=bs, block_mask=am)
Bm = dbcsr.create(B, mesh=mesh, grid=grid, block_size=bs, block_mask=bm)
Cm = dbcsr.multiply(Am, Bm, mesh=mesh, algorithm="cannon", densify=False,
                    local_kernel="ref")
out["dbcsr_blocked_sparse"] = float(np.max(np.abs(np.asarray(Cm.data) - ref)))
sym = (am.astype(np.int64) @ bm.astype(np.int64)) > 0
out["dbcsr_mask_matches"] = bool((Cm.block_mask == sym).all())
# numeric support is contained in the symbolic mask
Cb = np.asarray(Cm.data).reshape(M // bs, bs, N // bs, bs)
support = np.abs(Cb).max(axis=(1, 3)) > 0
out["support_in_mask"] = bool((support <= sym).all())
print("JSON" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def sparse_battery():
    stdout = run_subprocess_devices(SPARSE_BATTERY, n_devices=8, timeout=900)
    line = [l for l in stdout.splitlines() if l.startswith("JSON")][-1]
    return json.loads(line[4:])


@pytest.mark.parametrize("key", [
    "cannon_sparse_2x2", "summa_psum_sparse_2x2", "summa_gather_sparse_2x2",
    "summa_psum_sparse_2x1", "cannon25d_sparse", "dbcsr_blocked_sparse",
])
def test_distributed_sparse_matches_masked_dense(sparse_battery, key):
    assert sparse_battery[key] < 2e-4, (key, sparse_battery[key])


def test_distributed_sparse_mask_flow(sparse_battery):
    assert sparse_battery["dbcsr_mask_matches"]
    assert sparse_battery["support_in_mask"]
