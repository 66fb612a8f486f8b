"""The paper's two benchmark workloads, end to end on a host-device mesh:

  * square multiplication  -> Cannon's algorithm (O(1/sqrt(P)) comm)
  * tall-and-skinny        -> the O(1)-communication algorithm

with the SUMMA (ScaLAPACK PDGEMM analogue) baseline timed next to each,
and the blocked vs densified local-multiply comparison.

    PYTHONPATH=src python examples/distributed_matmul.py
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=16")

import time

import numpy as np
import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.core.blocking import GridSpec
from repro.core.multiply import distributed_matmul
from repro.core.tall_skinny import classify_shape
from repro.launch.mesh import make_mesh
from repro.planner import plan_multiply


def timed(tag, fn, *args):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(3):
        out = fn(*args)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / 3
    print(f"  {tag:34s} {dt*1e3:9.2f} ms")
    return out, dt


def main():
    mesh = make_mesh((4, 4), ("data", "model"))
    grid = GridSpec("data", "model")
    sh = NamedSharding(mesh, P("data", "model"))
    rng = np.random.RandomState(0)

    print("== square multiplication (paper: 63'360^3; scaled) ==")
    n = 1408
    A = rng.randn(n, n).astype(np.float32)
    B = rng.randn(n, n).astype(np.float32)
    Ad, Bd = jax.device_put(A, sh), jax.device_put(B, sh)
    # algorithm="auto" routes through the cost-model planner
    # (repro.planner.plan_multiply); return_plan exposes the decision,
    # and plan.explain() prints the per-candidate predicted costs, e.g.:
    #
    #   plan: cannon + densified  occupancy=1  predicted=1.4 ms
    #     candidate          comm_ms  compute_ms  overhead_ms  total_ms
    #   * cannon+densified     0.79      0.39        0.21        1.40
    #     summa+densified      1.59      0.39        0.41        2.39
    #     ts_k+densified       3.17      0.39        0.21        3.77
    #     ...
    #     cannon25d+densified     -         -           -           -
    #                           infeasible: no replication axis
    print(plan_multiply(n, n, n, mesh_shape=(4, 4)).explain())
    # one TRACED run: the multiply's phase spans land in a jax.profiler
    # trace beside the device ops (open artifacts/obs/profile in
    # TensorBoard's profile plugin or ui.perfetto.dev), and the
    # telemetry layer (repro.obs) records them for its breakdown
    obs.enable(log_dir="artifacts/obs")
    with jax.profiler.trace("artifacts/obs/profile"):
        _, xplan = distributed_matmul(Ad, Bd, mesh=mesh, grid=grid,
                                      return_plan=True)
    print("  profiled timeline (dbcsr.* spans; full trace -> "
          "artifacts/obs/profile):")
    print(obs.render_timeline(obs.profile_spans("artifacts/obs/profile")))
    print(obs.render_breakdown(obs.last_trace()))
    obs.disable()  # timed comparisons below run with zero overhead
    c1, t_auto = timed("auto (planner)", jax.jit(
        lambda a, b: distributed_matmul(a, b, mesh=mesh, grid=grid)), Ad, Bd)
    c2, t_summa = timed("SUMMA (PDGEMM baseline)", jax.jit(
        lambda a, b: distributed_matmul(a, b, mesh=mesh, grid=grid,
                                        algorithm="summa")), Ad, Bd)
    print(f"  speedup vs PDGEMM: {t_summa/t_auto:.2f}x   "
          f"agreement: {float(np.max(np.abs(np.asarray(c1)-np.asarray(c2)))):.1e}")

    print("== tall-and-skinny (paper: 1'408 x 1'982'464; scaled) ==")
    m = nn = 352
    k = 45056
    A2 = rng.randn(m, k).astype(np.float32)
    B2 = rng.randn(k, nn).astype(np.float32)
    print(f"  shape-only classification: {classify_shape(m, k, nn)}")
    A2d = jax.device_put(A2, NamedSharding(mesh, P(None, ("data", "model"))))
    B2d = jax.device_put(B2, NamedSharding(mesh, P(("data", "model"), None)))
    print(plan_multiply(m, k, nn, mesh_shape=(4, 4)).explain())
    c3, t_ts = timed("auto (planner)", jax.jit(
        lambda a, b: distributed_matmul(a, b, mesh=mesh, grid=grid)),
        A2d, B2d)
    A2s, B2s = jax.device_put(A2, sh), jax.device_put(B2, sh)
    c4, t_sm = timed("SUMMA (PDGEMM baseline)", jax.jit(
        lambda a, b: distributed_matmul(a, b, mesh=mesh, grid=grid,
                                        algorithm="summa")), A2s, B2s)
    print(f"  speedup vs PDGEMM: {t_sm/t_ts:.2f}x  "
          "(paper reports up to 2.5x on this shape)")

    # traced tall-skinny run: every traced multiply also logs the
    # planner's predicted cost next to the measured dispatch time
    # (artifacts/obs/plan_outcomes.jsonl — the input to
    #  `python -m repro.planner.calibrate --check-drift`)
    obs.enable(log_dir="artifacts/obs", reset=False)
    distributed_matmul(A2d, B2d, mesh=mesh, grid=grid)
    obs.disable()
    print("== planner scoreboard (predicted vs measured) ==")
    print(obs.render_scoreboard(obs.planner_scoreboard(obs.plan_outcomes())))


if __name__ == "__main__":
    main()
