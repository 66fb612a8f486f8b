"""Batched multiply stack: grouped executor oracle, fuse-or-loop
planner, ``dbcsr.multiply_batched`` bit-identity vs the looped path,
and the continuous-batching service.

Single-device tests run inline; the multi-device bit-identity battery
runs on a 2x2 host mesh in one subprocess (conftest pattern).
"""
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import run_subprocess_devices

from repro.compat import make_mesh
from repro.core import dbcsr
from repro.core.engine import batched_stack_executor, stack_executor
from repro.planner.cost_model import (
    BATCHED_ALGORITHMS, HardwareModel, Problem, batched_dispatch_cost,
    candidate_cost)
from repro.planner.plan import (
    plan_cache_clear, plan_cache_stats, plan_multiply, plan_multiply_batched)


def _mesh11():
    return make_mesh((1, 1), ("data", "model"))


def _rand_mask(rng, nbr, nbc, fill):
    if fill >= 1.0:
        return None
    mask = rng.rand(nbr, nbc) < fill
    mask[0, 0] = True            # keep at least one block
    return mask


# ---------------------------------------------------------------------------
# grouped executor oracle: fused batch vs per-group executors, bitwise
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fills", [(1.0, 1.0, 1.0), (1.0, 0.5, 0.05)])
def test_batched_executor_matches_per_group(rng, fills):
    m, k, n = 128, 192, 64
    bm, bk, bn = 32, 32, 32
    g = len(fills)
    a = rng.randn(g, m, k).astype(np.float32)
    b = rng.randn(g, k, n).astype(np.float32)
    group_masks = []
    for gi, fill in enumerate(fills):
        am = _rand_mask(rng, m // bm, k // bk, fill)
        if am is not None:
            a[gi] *= np.repeat(np.repeat(am, bm, 0), bk, 1)
        group_masks.append({} if am is None else {"a_mask": am})
    fused = batched_stack_executor(
        g, m, k, n, block_m=bm, block_k=bk, block_n=bn,
        kernel="ref", group_masks=group_masks)
    got = np.asarray(fused(jnp.asarray(a), jnp.asarray(b)))
    for gi in range(g):
        solo = stack_executor(
            m, k, n, block_m=bm, block_k=bk, block_n=bn, kernel="ref",
            stack_size=fused.stack_size, align=fused.align,
            **group_masks[gi])
        want = np.asarray(solo(jnp.asarray(a[gi]), jnp.asarray(b[gi])))
        assert np.array_equal(got[gi], want), (gi, fills)


def test_batched_executor_smm_kernel(rng):
    # the Pallas-backed smm path against the ref path (allclose — the
    # kernels differ in accumulation instruction, not semantics)
    g, m, k, n = 2, 64, 64, 64
    a = jnp.asarray(rng.randn(g, m, k).astype(np.float32))
    b = jnp.asarray(rng.randn(g, k, n).astype(np.float32))
    f_smm = batched_stack_executor(g, m, k, n, block_m=32, block_k=32,
                                   block_n=32, kernel="smm")
    f_ref = batched_stack_executor(g, m, k, n, block_m=32, block_k=32,
                                   block_n=32, kernel="ref")
    np.testing.assert_allclose(np.asarray(f_smm(a, b)),
                               np.asarray(f_ref(a, b)), atol=1e-4)


def test_batched_plan_stats(rng):
    g = 3
    masks = [{}, {}, {"a_mask": _rand_mask(rng, 4, 4, 0.4)}]
    f = batched_stack_executor(g, 128, 128, 128, block_m=32, block_k=32,
                               block_n=32, kernel="ref", group_masks=masks)
    st = f.batched_plan.stats()
    assert st["n_groups"] == g
    assert len(st["per_group"]) == g
    # the two dense groups share one memoized plan
    assert st["n_shared_plans"] < g
    # sparse group padded up to the dense groups' pow-2 stack shape
    assert st["n_padding"] > 0
    assert 0.0 < st["padding_frac"] < 1.0
    assert st["n_entries"] + st["n_padding"] \
        == f.batched_plan.triples.shape[0] * f.batched_plan.triples.shape[1]


# ---------------------------------------------------------------------------
# planner: fuse-or-loop pricing, cache stats, summa-gather memory gate
# ---------------------------------------------------------------------------

def test_plan_multiply_batched_fuse_decision():
    # many small same-geometry requests: trace/launch amortization wins
    bp = plan_multiply_batched(16, 256, 256, 256, mesh_shape=(1, 1))
    assert bp.fuse and bp.n_requests == 16
    assert bp.algorithm in BATCHED_ALGORITHMS
    assert bp.predicted_speedup > 1.0
    assert "FUSE" in bp.explain()
    # nothing to amortize for a single request
    assert not plan_multiply_batched(1, 256, 256, 256).fuse
    # empty product -> trivial, never fused
    assert not plan_multiply_batched(8, 256, 256, 256, occupancy=0.0).fuse
    with pytest.raises(ValueError):
        plan_multiply_batched(4, 256, 256, 256, algorithm="cannon25d")


def test_batched_dispatch_cost_padding_penalty():
    hw = HardwareModel()
    prob = Problem(512, 512, 512, 64, 64, 64, 1.0, 4, 1, 1)
    chosen = candidate_cost(hw, prob, "cannon", True)
    fused0, looped = batched_dispatch_cost(hw, chosen, 8, 0.0)
    fused_padded, _ = batched_dispatch_cost(hw, chosen, 8, 0.5)
    assert fused0 < looped           # amortization wins without padding
    assert fused_padded > fused0     # padding priced as wasted compute


def test_plan_cache_stats():
    plan_cache_clear()
    s0 = plan_cache_stats()
    assert s0["hits"] == s0["misses"] == s0["evictions"] == 0
    plan_multiply(384, 384, 384)
    plan_multiply(384, 384, 384)
    s1 = plan_cache_stats()
    assert s1["misses"] >= 1 and s1["hits"] >= 1
    assert s1["currsize"] >= 1
    assert s1["evictions"] == max(s1["misses"] - s1["currsize"], 0)


def test_summa_gather_memory_gate():
    hw = HardwareModel()
    prob = Problem(1024, 1024, 1024, 64, 64, 64, 1.0, 4, 4, 4)
    gather = candidate_cost(hw, prob, "summa_gather", True)
    summa = candidate_cost(hw, prob, "summa", True)
    # gathered full-K panels: sqrt(P)-fold operand replication
    assert gather.mem_bytes > 2 * summa.mem_bytes
    ml, nl, e = 1024 // 4, 1024 // 4, 4
    assert gather.mem_bytes == (ml * 1024 + 1024 * nl + ml * nl) * e
    # the gate trips when the replicas don't fit
    tight = HardwareModel(mem_bytes=float(gather.mem_bytes) - 1.0)
    assert not candidate_cost(tight, prob, "summa_gather", True).feasible
    assert candidate_cost(tight, prob, "summa", True).feasible
    # pinned summa+gather plans through the replication-aware model
    plan = plan_multiply(1024, 1024, 1024, mesh_shape=(4, 4),
                         algorithm="summa_gather", hw=hw)
    assert plan.chosen is not None
    assert plan.chosen.algorithm == "summa_gather"
    assert plan.chosen.mem_bytes == gather.mem_bytes


# ---------------------------------------------------------------------------
# dbcsr api: bucket key, add(recompute_norms), batched vs looped
# ---------------------------------------------------------------------------

def test_bucket_key_contract(rng):
    mesh = _mesh11()
    A = rng.randn(128, 128).astype(np.float32)
    a = dbcsr.create(A, mesh=mesh, block_size=64)
    b = dbcsr.create(A, mesh=mesh, block_size=64)
    assert dbcsr._bucket_key(a, b, None) == dbcsr._bucket_key(b, a, None)
    # eps is part of the key
    assert dbcsr._bucket_key(a, b, 1e-3) != dbcsr._bucket_key(a, b, None)
    # occupancy bin is part of the key
    mask = np.zeros((2, 2), bool)
    mask[0, 0] = True
    a_sp = dbcsr.create(A, mesh=mesh, block_size=64, block_mask=mask)
    assert dbcsr._bucket_key(a_sp, b, None) != dbcsr._bucket_key(a, b, None)
    # geometry is part of the key
    c = dbcsr.create(rng.randn(128, 256).astype(np.float32),
                     mesh=mesh, block_size=64)
    assert dbcsr._bucket_key(a, c, None) != dbcsr._bucket_key(a, b, None)


def test_add_recompute_norms(rng):
    mesh = _mesh11()
    A = rng.randn(128, 128).astype(np.float32)
    B = rng.randn(128, 128).astype(np.float32)
    a = dbcsr.create(A, mesh=mesh, block_size=64, compute_norms=True)
    b = dbcsr.create(B, mesh=mesh, block_size=64, compute_norms=True)
    lazy = dbcsr.add(a, b)
    assert lazy.block_norms is None      # default: cache stays empty
    eager = dbcsr.add(a, b, recompute_norms=True)
    assert eager.block_norms is not None
    np.testing.assert_allclose(eager.block_norms, lazy.norms(), rtol=1e-6)


def _exact_mask(rng, nbr, nbc, fill):
    """A mask with exactly ``round(fill * nbr * nbc)`` present blocks at
    places drawn from ``rng``: its fill bin is the one the caller states,
    whatever state the session's shared ``rng`` arrives in."""
    if fill >= 1.0:
        return None
    mask = np.zeros(nbr * nbc, dtype=bool)
    mask[rng.permutation(nbr * nbc)[:round(fill * nbr * nbc)]] = True
    return mask.reshape(nbr, nbc)


def _make_requests(rng, mesh, geoms_fills, block_size=32):
    reqs, refs = [], []
    for (m, k, n), fill in geoms_fills:
        A = rng.randn(m, k).astype(np.float32)
        B = rng.randn(k, n).astype(np.float32)
        am = _exact_mask(rng, m // block_size, k // block_size, fill)
        a = dbcsr.create(A, mesh=mesh, block_size=block_size, block_mask=am)
        b = dbcsr.create(B, mesh=mesh, block_size=block_size)
        reqs.append((a, b))
        refs.append((np.asarray(a.data), B))
    return reqs, refs


@pytest.mark.parametrize("algorithm", ["cannon", "summa"])
def test_multiply_batched_bit_identity_1x1(rng, algorithm):
    # the acceptance oracle: fused == looped BITWISE on the blocked
    # path at depth 1, eps 0, across fills and mixed geometries
    mesh = _mesh11()
    geoms_fills = [
        ((128, 96, 64), 1.0), ((128, 96, 64), 1.0),   # same bucket
        ((128, 96, 64), 0.5), ((128, 96, 64), 0.05),  # other fill bins
        ((64, 64, 128), 1.0),                         # other geometry
    ]
    reqs, refs = _make_requests(rng, mesh, geoms_fills)
    kw = dict(mesh=mesh, algorithm=algorithm, densify=False,
              local_kernel="ref", pipeline_depth=1)
    fused, report = dbcsr.multiply_batched(reqs, fused=True,
                                           return_plan=True, **kw)
    looped = dbcsr.multiply_batched(reqs, fused=False, **kw)
    assert report["n_buckets"] == 4
    assert report["n_fused_requests"] == len(reqs)
    for i, (c_f, c_l) in enumerate(zip(fused, looped)):
        assert np.array_equal(np.asarray(c_f.data), np.asarray(c_l.data)), i
        Am, B = refs[i]
        np.testing.assert_allclose(np.asarray(c_f.data), Am @ B, atol=1e-3)


def test_multiply_batched_auto_and_filter(rng):
    mesh = _mesh11()
    geoms_fills = [((128, 128, 128), 1.0)] * 3
    reqs, refs = _make_requests(rng, mesh, geoms_fills, block_size=64)
    # planner-driven fuse decision (algorithm/densify free)
    out, report = dbcsr.multiply_batched(reqs, mesh=mesh, return_plan=True)
    for i, c in enumerate(out):
        Am, B = refs[i]
        np.testing.assert_allclose(np.asarray(c.data), Am @ B, atol=1e-3)
    assert report["buckets"][0]["plan"] is not None
    # eps filtering: fused result support == looped result support
    eps = 1e-2
    f_eps = dbcsr.multiply_batched(reqs, mesh=mesh, algorithm="cannon",
                                   densify=False, local_kernel="ref",
                                   filter_eps=eps, fused=True,
                                   pipeline_depth=1)
    l_eps = [dbcsr.multiply(a, b, mesh=mesh, algorithm="cannon",
                            densify=False, local_kernel="ref",
                            filter_eps=eps, pipeline_depth=1)
             for a, b in reqs]
    for c_f, c_l in zip(f_eps, l_eps):
        assert np.array_equal(c_f.block_mask, c_l.block_mask)
        assert np.array_equal(np.asarray(c_f.data), np.asarray(c_l.data))


def test_multiply_batched_gather_rejected(rng):
    mesh = _mesh11()
    reqs, _ = _make_requests(rng, mesh, [((64, 64, 64), 1.0)] * 2)
    with pytest.raises(ValueError):
        dbcsr.multiply_batched(reqs, mesh=mesh, algorithm="summa",
                               bcast="gather", fused=True)
    # unpinned it degrades to the looped path instead of raising
    out = dbcsr.multiply_batched(reqs, mesh=mesh, algorithm="summa",
                                 bcast="gather")
    assert len(out) == 2


# ---------------------------------------------------------------------------
# serving layer: SLO/max_batch draining with an injected clock
# ---------------------------------------------------------------------------

class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_multiply_service(rng):
    from repro.serve.multiply_service import MultiplyService

    mesh = _mesh11()
    clk = FakeClock()
    svc = MultiplyService(mesh, slo_s=1.0, max_batch=4, clock=clk,
                          algorithm="cannon", densify=False,
                          local_kernel="ref", pipeline_depth=1)
    reqs, refs = _make_requests(
        rng, mesh, [((128, 128, 128), 1.0)] * 6, block_size=64)
    tickets = [svc.submit(a, b) for a, b in reqs]
    # full bucket (max_batch=4) fires immediately; 2 wait on the SLO
    done = svc.poll()
    assert sorted(done) == tickets[:4]
    assert svc.n_pending == 2
    clk.t = 0.5
    assert svc.poll() == []          # inside the SLO window: keep waiting
    clk.t = 1.01
    assert sorted(svc.poll()) == tickets[4:]
    assert svc.n_pending == 0
    for t, (Am, B) in zip(tickets, refs):
        np.testing.assert_allclose(np.asarray(svc.result(t).data),
                                   Am @ B, atol=1e-3)
    st = svc.stats()
    assert st["n_requests"] == 6 and st["n_dispatches"] == 2
    assert st["n_fused_requests"] == 6
    assert st["latency_p99_s"] >= st["latency_p50_s"] >= 0.0
    # flush drains regardless of SLO; result() pops
    t7 = svc.submit(*reqs[0])
    assert svc.flush() == [t7]
    svc.result(t7)
    with pytest.raises(KeyError):
        svc.result(t7)


def test_multiply_service_bucketing(rng):
    from repro.serve.multiply_service import MultiplyService

    mesh = _mesh11()
    clk = FakeClock()
    svc = MultiplyService(mesh, slo_s=0.0, max_batch=8, clock=clk,
                          algorithm="cannon", densify=False,
                          local_kernel="ref", pipeline_depth=1)
    reqs, _ = _make_requests(
        rng, mesh, [((64, 64, 64), 1.0), ((64, 64, 128), 1.0),
                    ((64, 64, 64), 1.0)])
    for a, b in reqs:
        svc.submit(a, b)
    # slo_s=0: everything due on the first poll, but in TWO dispatches
    # (two geometry buckets)
    done = svc.poll()
    assert sorted(done) == [0, 1, 2]
    assert svc.stats()["n_dispatches"] == 2


# ---------------------------------------------------------------------------
# multi-device battery: fused == looped bitwise on a 2x2 mesh
# ---------------------------------------------------------------------------

BATTERY = r"""
import json
import numpy as np, jax, jax.numpy as jnp

from repro.compat import make_mesh
from repro.core import dbcsr

rng = np.random.RandomState(0)
out = {}
mesh = make_mesh((2, 2), ("data", "model"))

def requests(geoms_fills, bs):
    reqs, refs = [], []
    for (m, k, n), fill in geoms_fills:
        A = rng.randn(m, k).astype(np.float32)
        B = rng.randn(k, n).astype(np.float32)
        am = None
        if fill < 1.0:
            am = rng.rand(m // bs, k // bs) < fill
            am[0, 0] = True
        a = dbcsr.create(A, mesh=mesh, block_size=bs, block_mask=am)
        b = dbcsr.create(B, mesh=mesh, block_size=bs)
        reqs.append((a, b)); refs.append((np.asarray(a.data), B))
    return reqs, refs

geoms = [((128, 128, 64), 1.0), ((128, 128, 64), 1.0),
         ((128, 128, 64), 0.5), ((128, 128, 64), 0.05),
         ((64, 128, 128), 1.0)]
for algo in ("cannon", "summa"):
    reqs, refs = requests(geoms, 32)
    kw = dict(mesh=mesh, algorithm=algo, densify=False,
              local_kernel="ref", pipeline_depth=1)
    fused, rep = dbcsr.multiply_batched(reqs, fused=True, return_plan=True,
                                        **kw)
    looped = dbcsr.multiply_batched(reqs, fused=False, **kw)
    out[f"{algo}_bitwise"] = max(
        float(np.max(np.abs(np.asarray(f.data) - np.asarray(l.data)))
              if not np.array_equal(np.asarray(f.data), np.asarray(l.data))
              else 0.0)
        for f, l in zip(fused, looped))
    out[f"{algo}_exact"] = all(
        np.array_equal(np.asarray(f.data), np.asarray(l.data))
        for f, l in zip(fused, looped))
    out[f"{algo}_ref"] = max(
        float(np.max(np.abs(np.asarray(f.data) - Am @ B)))
        for f, (Am, B) in zip(fused, refs))
    out[f"{algo}_fused_requests"] = rep["n_fused_requests"]

# densified fused path (allclose contract, not bitwise)
reqs, refs = requests([((128, 128, 128), 1.0)] * 4, 64)
dens = dbcsr.multiply_batched(reqs, mesh=mesh, algorithm="cannon",
                              densify=True, fused=True)
out["densified_ref"] = max(
    float(np.max(np.abs(np.asarray(c.data) - Am @ B)))
    for c, (Am, B) in zip(dens, refs))

print("JSON" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def battery_results():
    stdout = run_subprocess_devices(BATTERY, n_devices=4, timeout=900)
    line = [l for l in stdout.splitlines() if l.startswith("JSON")][-1]
    return json.loads(line[4:])


@pytest.mark.parametrize("algo", ["cannon", "summa"])
def test_distributed_batched_bit_identity(battery_results, algo):
    assert battery_results[f"{algo}_exact"] is True, \
        battery_results[f"{algo}_bitwise"]
    assert battery_results[f"{algo}_fused_requests"] == 5
    assert battery_results[f"{algo}_ref"] < 2e-4


def test_distributed_batched_densified(battery_results):
    assert battery_results["densified_ref"] < 2e-4
