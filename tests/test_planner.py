"""Cost-model planner: model sanity, plan cache, and auto-vs-fixed
oracle equivalence (the ISSUE-3 acceptance battery).

Model-level tests pin ``hw`` to DEFAULT_HARDWARE so they are
independent of whatever calibration file a previous bench run left
behind; the distributed battery runs in a 4-device subprocess like
test_distributed.py.
"""
import json

import numpy as np
import pytest

from conftest import run_subprocess_devices

from repro.core.tall_skinny import (DEFAULT_TS_RATIO, classify_shape,
                                    ts_classify_ratio)
from repro.kernels.smm.autotune import best_params_for, best_params_meta
from repro.planner import cost_model
from repro.planner.cost_model import (DEFAULT_HARDWARE, Problem,
                                      candidate_cost, ts_crossover_ratio)
from repro.planner.plan import plan_multiply

HW = DEFAULT_HARDWARE


# ---------------------------------------------------------------------------
# cost-model sanity
# ---------------------------------------------------------------------------


def test_cannon_cost_monotone_in_comm_volume():
    """Growing K grows Cannon's shifted volume (m*k + k*n)/pg and the
    predicted comm cost with it, monotonically."""
    costs = [candidate_cost(HW, Problem(1024, k, 1024, 64, 64, 64, 1.0,
                                        4, 2, 2), "cannon", True)
             for k in (1024, 2048, 4096, 8192)]
    assert all(c.feasible for c in costs)
    comms = [c.comm_s for c in costs]
    assert comms == sorted(comms) and comms[0] < comms[-1]
    totals = [c.total_s for c in costs]
    assert totals == sorted(totals)


def test_cannon_cost_scales_with_bandwidth():
    slow = HW.replace(bytes_per_s=HW.bytes_per_s / 10)
    prob = Problem(2048, 2048, 2048, 64, 64, 64, 1.0, 4, 2, 2)
    assert candidate_cost(slow, prob, "cannon", True).comm_s > \
        candidate_cost(HW, prob, "cannon", True).comm_s


def test_25d_beats_cannon_only_when_memory_allows():
    """2.5D halves the shift steps (cheaper) but replicates operands
    c-fold; when the replicas don't fit, the planner must fall back to
    plain Cannon — the model's memory gate is what decides."""
    hw = HW.replace(latency_s=1e-6, bytes_per_s=1e11)
    kw = dict(blocks=(64, 64, 64), mesh_shape=(4, 4, 2), densify=True)
    ample = plan_multiply(8192, 8192, 8192, hw=hw, **kw)
    assert ample.algorithm == "cannon25d" and ample.c_repl == 2
    c25 = next(c for c in ample.candidates if c.algorithm == "cannon25d")
    ca = next(c for c in ample.candidates if c.algorithm == "cannon")
    assert c25.total_s < ca.total_s
    assert c25.mem_bytes > ca.mem_bytes  # the replication charge

    # per-device 2D shards fit (~50 MB) but the 2.5D replicas (~84 MB)
    # do not -> cannon25d infeasible, cannon chosen
    tight = plan_multiply(8192, 8192, 8192,
                          hw=hw.replace(mem_bytes=60e6), **kw)
    assert tight.algorithm == "cannon"
    c25 = next(c for c in tight.candidates if c.algorithm == "cannon25d")
    assert not c25.feasible and "GB/device" in c25.reason


def test_tall_skinny_picked_for_8_to_1_shapes():
    for m, k, n, family in [(512, 4096, 512, "ts_k"),
                            (4096, 512, 512, "ts_m"),
                            (512, 512, 4096, "ts_n")]:
        plan = plan_multiply(m, k, n, blocks=(64, 64, 64),
                             mesh_shape=(2, 2), hw=HW)
        assert plan.algorithm == family, (m, k, n, plan.algorithm)


def test_forced_algorithm_and_path_are_honoured():
    plan = plan_multiply(1024, 1024, 1024, blocks=(64, 64, 64),
                         mesh_shape=(2, 2), algorithm="summa",
                         densify=False, hw=HW)
    assert plan.algorithm == "summa" and plan.densify is False
    assert plan.stack_tile is not None and plan.align is not None
    assert plan.params_source is not None


def test_explain_lists_candidates():
    plan = plan_multiply(1024, 1024, 1024, blocks=(64, 64, 64),
                         mesh_shape=(2, 2), hw=HW)
    text = plan.explain()
    assert text.startswith("plan:")
    for label in ("cannon+densified", "summa+blocked", "ts_k+densified"):
        assert label in text
    assert "infeasible" in text  # cannon25d on a 2D mesh


# ---------------------------------------------------------------------------
# plan cache + trivial plan (the masks_empty short-circuit)
# ---------------------------------------------------------------------------


def test_plan_cache_second_call_zero_evaluations():
    kw = dict(blocks=(32, 32, 32), mesh_shape=(2, 2), occupancy=0.37,
              hw=HW)
    first = plan_multiply(640, 640, 640, **kw)
    before = cost_model.N_EVALS
    second = plan_multiply(640, 640, 640, **kw)
    assert cost_model.N_EVALS == before, "cache hit must not re-evaluate"
    assert second is first


def test_zero_occupancy_returns_trivial_plan_without_evaluations():
    before = cost_model.N_EVALS
    plan = plan_multiply(256, 256, 256, blocks=(16, 16, 16),
                         mesh_shape=(2, 2), occupancy=0.0, hw=HW)
    assert plan.trivial and plan.predicted_s == 0.0
    assert plan.candidates == ()
    assert cost_model.N_EVALS == before, \
        "empty product must not touch the cost model (divide-by-zero)"
    # the blocked path (which skips everything) is preferred when the
    # geometry admits it
    assert plan.densify is False


def test_blocked_cost_rejects_zero_occupancy():
    with pytest.raises(ValueError, match="occupancy"):
        candidate_cost(HW, Problem(256, 256, 256, 16, 16, 16, 0.0,
                                   4, 2, 2), "cannon", False)


# ---------------------------------------------------------------------------
# rank imbalance pricing + rebalance arming (ISSUE 9)
# ---------------------------------------------------------------------------


def test_rebalance_armed_on_imbalanced_blocked_plan():
    kw = dict(blocks=(64, 64, 64), mesh_shape=(2, 2), occupancy=0.05,
              densify=False, hw=HW)
    plan = plan_multiply(4096, 4096, 4096, **kw, rank_imbalance=4.0)
    assert plan.rank_imbalance == pytest.approx(4.0)
    assert plan.rebalance, "4x imbalance at 5% fill should arm rebalance"
    assert plan.rebalance_saved_s > plan.rebalance_cost_s > 0.0
    assert "imbal" in plan.explain()


def test_rebalance_declined_when_balanced():
    kw = dict(blocks=(64, 64, 64), mesh_shape=(2, 2), occupancy=0.05,
              densify=False, hw=HW)
    uniform = plan_multiply(4096, 4096, 4096, **kw, rank_imbalance=1.0)
    assert not uniform.rebalance and uniform.rebalance_saved_s == 0.0
    unknown = plan_multiply(4096, 4096, 4096, **kw)
    assert not unknown.rebalance, \
        "no imbalance estimate must mean no speculative permutation"
    # distinct imbalances must not collide in the plan cache
    assert unknown is not uniform


def test_imbalance_inflates_blocked_candidate_cost():
    prob = Problem(4096, 4096, 4096, 64, 64, 64, 0.05, 4, 2, 2)
    union = candidate_cost(HW, prob, "cannon", False)
    flat = candidate_cost(HW, prob, "cannon", False, rank_imbalance=1.0)
    skew = candidate_cost(HW, prob, "cannon", False, rank_imbalance=3.0)
    assert skew.total_s > flat.total_s, \
        "busiest-rank pricing should inflate the imbalanced candidate"
    # rank-exact pricing at 5% fill on 4 ranks undercuts the legacy
    # union inflation (1 - 0.95^4) even at 3x imbalance
    assert flat.total_s < skew.total_s < union.total_s
    dense_flat = candidate_cost(HW, prob, "cannon", True)
    dense_skew = candidate_cost(HW, prob, "cannon", True, rank_imbalance=3.0)
    assert dense_skew.total_s == pytest.approx(dense_flat.total_s), \
        "densified execution is occupancy-blind; imbalance must not price it"


# ---------------------------------------------------------------------------
# planner-owned classify threshold + winners-table metadata
# ---------------------------------------------------------------------------


def test_ts_classify_ratio_exported_and_consistent():
    ratio = ts_classify_ratio()
    assert 2.0 <= ratio <= 64.0
    # classification must agree with the exported threshold exactly
    for m, k, n in [(100, 150, 80), (64, 4096, 64), (63360,) * 3,
                    (1408, 1982464, 1408)]:
        algo = classify_shape(m, k, n)
        dims = {"m": m, "k": k, "n": n}
        big = max(dims, key=dims.get)
        others = max(v for kk, v in dims.items() if kk != big)
        assert (algo == f"ts_{big}") == (dims[big] >= ratio * others)
    # explicit ratio still overrides (legacy call sites)
    assert classify_shape(64, 512, 64, ratio=DEFAULT_TS_RATIO) == "ts_k"
    assert classify_shape(64, 500, 64, ratio=DEFAULT_TS_RATIO) == "cannon"


def test_ts_crossover_ratio_bounds():
    # clamped to [2, 64] (or the legacy 8.0 fallback) for any constants
    for hw in (HW,
               HW.replace(bytes_per_s=HW.bytes_per_s * 100),
               HW.replace(latency_s=1e-6, bytes_per_s=1e11),
               HW.replace(latency_s=0.0)):
        assert 2.0 <= ts_crossover_ratio(hw) <= 64.0
    # higher per-message latency penalises Cannon's O(pg) messages and
    # pulls the tall-skinny crossover in, never out
    slow_lat = HW.replace(latency_s=HW.latency_s * 100)
    assert ts_crossover_ratio(slow_lat) <= ts_crossover_ratio(HW)


def test_best_params_meta_provenance(tmp_path):
    # unknown geometry -> heuristic, with align/stack_tile equal to the
    # legacy tuple lookup
    meta = best_params_meta(99, 99, 99, str(tmp_path / "none.json"))
    assert meta["source"] == "heuristic"
    assert (meta["align"], meta["stack_tile"]) == \
        best_params_for(99, 99, 99, str(tmp_path / "none.json"))
    # recorded winners surface their key and measured throughput
    cache = {"64": {"best": {"align": True, "stack_tile": 4096,
                             "gflops": 12.5}}}
    path = tmp_path / "tab.json"
    path.write_text(json.dumps(cache))
    meta = best_params_meta(64, 64, 64, str(path))
    assert meta["source"] == "winners[64]" and meta["gflops"] == 12.5
    # sparse bin falls back through the dense entry
    meta = best_params_meta(64, 64, 64, str(path), fill=0.05)
    assert meta["source"] == "winners[64]" and meta["bin"] == 0.05
    # non-uniform geometry
    assert best_params_meta(32, 64, 32)["source"] == "heuristic-nonuniform"


# ---------------------------------------------------------------------------
# distributed battery: auto oracle-equivalence vs every fixed algorithm
# ---------------------------------------------------------------------------

BATTERY = r"""
import json
import numpy as np, jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compat import make_mesh
from repro.core.blocking import GridSpec
from repro.core.multiply import distributed_matmul
from repro.core import dbcsr
from repro.planner import cost_model
from repro.planner.plan import plan_cache_info

rng = np.random.RandomState(0)
mesh = make_mesh((2, 2), ("data", "model"))
grid = GridSpec("data", "model")
sh = NamedSharding(mesh, P("data", "model"))
out = {}
M = K = N = 128
bs = 16
A = rng.randn(M, K).astype(np.float32)
B = rng.randn(K, N).astype(np.float32)

for fill in (1.0, 0.2):
    if fill < 1.0:
        am = rng.rand(M // bs, K // bs) < fill
        bm = rng.rand(K // bs, N // bs) < fill
        am[0, 0] = bm[0, 0] = True
        Az = A * np.repeat(np.repeat(am, bs, 0), bs, 1)
        Bz = B * np.repeat(np.repeat(bm, bs, 0), bs, 1)
    else:
        am = bm = None
        Az, Bz = A, B
    ref = Az @ Bz
    Ad, Bd = jax.device_put(Az, sh), jax.device_put(Bz, sh)
    kw = dict(mesh=mesh, grid=grid, block_m=bs, block_k=bs, block_n=bs,
              a_mask=am, b_mask=bm, local_kernel="ref")
    C_auto, plan = distributed_matmul(Ad, Bd, algorithm="auto",
                                      return_plan=True, **kw)
    tag = f"{fill:g}"
    out[f"auto_err_{tag}"] = float(np.max(np.abs(np.asarray(C_auto) - ref)))
    out[f"auto_algo_{tag}"] = plan.algorithm
    out[f"auto_densify_{tag}"] = plan.densify
    # every fixed algorithm, both local paths, must agree with auto
    for algo in ("cannon", "summa", "ts_k", "ts_m", "ts_n"):
        for dens in (True, False):
            C = distributed_matmul(Ad, Bd, algorithm=algo, densify=dens, **kw)
            out[f"{algo}_{'dens' if dens else 'blk'}_{tag}"] = float(
                np.max(np.abs(np.asarray(C) - ref)))

# repeated auto multiply: plan comes from the cache, zero evaluations
ev0 = cost_model.N_EVALS
hits0 = plan_cache_info().hits
C2, plan2 = distributed_matmul(Ad, Bd, algorithm="auto",
                               return_plan=True, **kw)
out["cache_evals_delta"] = cost_model.N_EVALS - ev0
out["cache_hits_delta"] = plan_cache_info().hits - hits0
out["cache_same_choice"] = (plan2.algorithm == plan.algorithm
                            and plan2.densify == plan.densify)

# dbcsr.multiply defaults through the planner and exposes the plan
Am = dbcsr.create(A, mesh=mesh, grid=grid, block_size=bs)
Bm = dbcsr.create(B, mesh=mesh, grid=grid, block_size=bs)
Cm, pl = dbcsr.multiply(Am, Bm, mesh=mesh, return_plan=True)
out["dbcsr_err"] = float(np.max(np.abs(np.asarray(Cm.data) - A @ B)))
out["dbcsr_algo"] = pl.algorithm
out["dbcsr_last_plan_is_plan"] = Cm.last_plan is pl

# disjoint masks -> empty product -> trivial plan, zero C, no evals
za = np.zeros((M // bs, K // bs), bool); za[:, 0] = True
zb = np.zeros((K // bs, N // bs), bool); zb[1, :] = True
Azz = A * np.repeat(np.repeat(za, bs, 0), bs, 1)
Bzz = B * np.repeat(np.repeat(zb, bs, 0), bs, 1)
ev0 = cost_model.N_EVALS
C0, plan0 = distributed_matmul(
    jax.device_put(Azz, sh), jax.device_put(Bzz, sh), mesh=mesh, grid=grid,
    block_m=bs, block_k=bs, block_n=bs, a_mask=za, b_mask=zb,
    local_kernel="ref", algorithm="auto", return_plan=True)
out["trivial"] = plan0.trivial
out["trivial_evals"] = cost_model.N_EVALS - ev0
out["trivial_max"] = float(np.max(np.abs(np.asarray(C0))))

print("JSON" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def battery():
    stdout = run_subprocess_devices(BATTERY, n_devices=4, timeout=900)
    line = [l for l in stdout.splitlines() if l.startswith("JSON")][-1]
    return json.loads(line[4:])


TOL = 2e-4


@pytest.mark.parametrize("fill", ["1", "0.2"])
def test_auto_matches_every_fixed_algorithm(battery, fill):
    assert battery[f"auto_err_{fill}"] < TOL
    for algo in ("cannon", "summa", "ts_k", "ts_m", "ts_n"):
        for path in ("dens", "blk"):
            key = f"{algo}_{path}_{fill}"
            assert battery[key] < TOL, (key, battery[key])


def test_auto_routed_through_planner(battery):
    # the planner picked a real algorithm (and a local path) per fill
    assert battery["auto_algo_1"] in ("cannon", "summa", "ts_k", "ts_m",
                                      "ts_n")
    assert battery["auto_algo_0.2"] in ("cannon", "summa", "ts_k", "ts_m",
                                        "ts_n")
    assert battery["dbcsr_algo"] == battery["auto_algo_1"]
    assert battery["dbcsr_err"] < TOL
    assert battery["dbcsr_last_plan_is_plan"]


def test_plan_cache_hit_in_dispatch_path(battery):
    assert battery["cache_evals_delta"] == 0
    assert battery["cache_hits_delta"] >= 1
    assert battery["cache_same_choice"]


def test_empty_product_trivial_plan_in_dispatch_path(battery):
    assert battery["trivial"] is True
    assert battery["trivial_evals"] == 0
    assert battery["trivial_max"] == 0.0
