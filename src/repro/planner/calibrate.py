"""Fit the planner's hardware constants from measured artifacts.

The cost models in ``cost_model.py`` are only as good as their
constants.  Three sources, later ones overriding earlier:

  1. ``cost_model.DEFAULT_HARDWARE`` — documented built-in defaults
     (sane for this CPU container, see HardwareModel docstring).
  2. ``fit_from_artifacts`` — the existing bench trajectory under
     ``artifacts/bench/``: kernels.json (dense GEMM and fused-smm
     rates), sparse_smoke.json / sparse.json (per-stack-entry overhead
     as the slope of dispatch time over triple count), densify.json
     (cross-check of the dense rate on the densified local path).
  3. ``artifacts/planner_calibration.json`` — constants written by this
     module's CLI or by ``micro_calibrate`` (benchmarks/bench_planner.py
     runs it so the regret gate judges the planner against constants
     measured on the same machine, same process).

``get_hardware_model()`` resolves the merge once and caches it; the
plan cache (plan.py) keys on the resolved HardwareModel value, so a
recalibration automatically invalidates stale plans.

    PYTHONPATH=src python -m repro.planner.calibrate [--micro]
"""
from __future__ import annotations

import argparse
import json
import os
from typing import Dict, Optional

import numpy as np

from .cost_model import DEFAULT_HARDWARE, HardwareModel

__all__ = [
    "DEFAULT_CALIBRATION",
    "DEFAULT_PLAN_LOG",
    "drift_report",
    "fit_from_artifacts",
    "micro_calibrate",
    "measure_overlap",
    "get_hardware_model",
    "save_calibration",
    "invalidate_cache",
]

DEFAULT_CALIBRATION = os.path.join("artifacts", "planner_calibration.json")
DEFAULT_BENCH_DIR = os.path.join("artifacts", "bench")

_CACHED: Optional[HardwareModel] = None


def _load_json(path: str):
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def fit_from_artifacts(bench_dir: str = DEFAULT_BENCH_DIR) -> Dict[str, float]:
    """Extract whatever constants the recorded bench artifacts support.

    Returns a (possibly empty) partial dict — communication constants
    cannot be fitted from these single-process artifacts and keep their
    defaults unless a calibration file / micro_calibrate provides them.
    """
    out: Dict[str, float] = {}

    kernels = _load_json(os.path.join(bench_dir, "kernels.json")) or []
    dense = [r["gflops"] for r in kernels if r.get("kernel") == "dense_dot"]
    if dense:
        out["flops_per_s"] = max(dense) * 1e9
    fused = [r["fused_gflops"] for r in kernels
             if r.get("kernel") == "smm_dispatch" and "fused_gflops" in r]
    if fused:
        out["smm_flops_per_s"] = max(fused) * 1e9

    # densified local path cross-check: effective big-GEMM rate incl.
    # the densify copies — keep the more conservative estimate
    densify = _load_json(os.path.join(bench_dir, "densify.json")) or []
    eff = [2.0 * r["m"] * r["k"] * r["n"] / r["t_densified_s"]
           for r in densify if r.get("t_densified_s")]
    if eff and "flops_per_s" in out:
        out["flops_per_s"] = min(out["flops_per_s"], max(eff))
    elif eff:
        out["flops_per_s"] = max(eff)

    # per-entry overhead: slope of sparse dispatch time over triple
    # count, net of the pure-flop time at the fitted smm rate
    sparse = (_load_json(os.path.join(bench_dir, "sparse.json"))
              or _load_json(os.path.join(bench_dir, "sparse_smoke.json")))
    if sparse and sparse.get("rows"):
        rows = sparse["rows"]
        nt = np.array([r["n_triples"] for r in rows], dtype=float)
        ts = np.array([r["t_sparse_s"] for r in rows], dtype=float)
        if len(rows) >= 2 and np.ptp(nt) > 0:
            slope = float(np.polyfit(nt, ts, 1)[0])
            block = int(sparse.get("block", 8))
            flop_per_entry = 2.0 * block ** 3 / out.get(
                "smm_flops_per_s", DEFAULT_HARDWARE.smm_flops_per_s)
            out["stack_entry_s"] = max(slope - flop_per_entry, 1e-8)
    return out


def micro_calibrate(mesh=None, grid=None, reps: int = 5) -> Dict[str, float]:
    """Measure constants live, in-process (seconds of work, not minutes).

    Times a dense dot for ``flops_per_s``, fused-executor runs at two
    block sizes for (``smm_flops_per_s``, ``stack_entry_s``) — two
    equations, two unknowns — and, when a multi-device ``mesh``/``grid``
    is given, a large and a tiny psum for (``bytes_per_s``,
    ``latency_s``) plus the schedule engine's achieved comm/compute
    overlap per algorithm (``measure_overlap`` -> ``overlap_*``).
    Intended for bench_planner and the CLI; library calls never trigger
    measurement implicitly.
    """
    import time

    import jax
    import jax.numpy as jnp

    def best_of(fn, *args):
        jax.block_until_ready(fn(*args))
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            best = min(best, time.perf_counter() - t0)
        return best

    out: Dict[str, float] = {}
    rng = np.random.RandomState(0)

    s = 384
    a = jnp.asarray(rng.randn(s, s).astype(np.float32))
    b = jnp.asarray(rng.randn(s, s).astype(np.float32))
    t = best_of(jax.jit(lambda a, b: a @ b), a, b)
    out["flops_per_s"] = 2.0 * s ** 3 / max(t, 1e-9)

    # two block sizes => separate the per-flop rate from the per-entry
    # overhead: slope_b = 2*b^3/F + E
    from repro.core.densify import to_blocks
    from repro.core.engine import build_executor_plan, execute_plan

    slopes = {}
    for block in (8, 16):
        nb = 8
        dim = block * nb
        af = jnp.asarray(rng.randn(dim, dim).astype(np.float32))
        bf = jnp.asarray(rng.randn(dim, dim).astype(np.float32))
        ab, bb = to_blocks(af, block, block), to_blocks(bf, block, block)
        c0 = jnp.zeros((nb * nb, block, block), jnp.float32)
        times = {}
        for fill in (1.0, 0.25):
            mask = None
            if fill < 1.0:
                mask = np.zeros(nb * nb, dtype=bool)
                mask[rng.choice(nb * nb, int(fill * nb * nb),
                                replace=False)] = True
                mask = mask.reshape(nb, nb)
            plan = build_executor_plan(dim, dim, dim, block, block, block,
                                       512, a_mask=mask)
            times[fill] = (best_of(jax.jit(
                lambda ab, bb, c0, p=plan: execute_plan(
                    p, ab, bb, c0, kernel="ref")), ab, bb, c0),
                plan.n_entries)
        (t_hi, n_hi), (t_lo, n_lo) = times[1.0], times[0.25]
        if n_hi > n_lo:
            slopes[block] = max((t_hi - t_lo) / (n_hi - n_lo), 1e-9)
    if len(slopes) == 2:
        s8, s16 = slopes[8], slopes[16]
        df = 2.0 * (16 ** 3 - 8 ** 3)
        if s16 > s8:
            out["smm_flops_per_s"] = df / (s16 - s8)
            out["stack_entry_s"] = max(s8 - 2.0 * 8 ** 3
                                       / out["smm_flops_per_s"], 1e-8)
        else:  # overhead-dominated regime: slope IS the entry cost
            out["stack_entry_s"] = s8
            out["smm_flops_per_s"] = DEFAULT_HARDWARE.smm_flops_per_s

    if mesh is not None and grid is not None and mesh.devices.size > 1:
        from jax.sharding import PartitionSpec as P

        axes = (grid.row_axis, grid.col_axis)
        spec = P(axes[0], axes[1])
        pr, pc = grid.grid_shape(mesh)

        # MARGINAL per-collective cost: a single timed jit call carries
        # ~0.1-1 ms of fixed dispatch overhead that every *multiply*
        # pays once, not once per collective — so time a chain of n
        # data-dependent psums against a chain of 1 and difference them
        def chain(n):
            # payload size rides on the input array; the body is the
            # same n-deep data-dependent psum chain either way
            def body(x):
                for i in range(n):
                    x = jax.lax.psum(x + np.float32(i), axes)
                return x

            return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(spec,),
                                         out_specs=P(None, None),
                                         check_vma=False))

        reps_n = 8
        tiny = jnp.ones((pr, pc), jnp.float32)
        dt_tiny = best_of(chain(reps_n), tiny) - best_of(chain(1), tiny)
        out["latency_s"] = max(dt_tiny / (reps_n - 1), 1e-7)
        side = 256
        big = jnp.ones((pr * side, pc * side), jnp.float32)
        dt_big = best_of(chain(reps_n), big) - best_of(chain(1), big)
        per_msg = max(dt_big / (reps_n - 1) - out["latency_s"], 1e-9)
        bytes_moved = 2.0 * side * side * 4  # per-device shard, both ways
        out["bytes_per_s"] = bytes_moved / per_msg

        # achieved comm/compute overlap of the schedule engine, judged
        # against the bandwidth just measured
        hw = DEFAULT_HARDWARE.replace(
            **{k: v for k, v in out.items()
               if k in DEFAULT_HARDWARE.to_dict()})
        out.update(measure_overlap(mesh, grid, reps=reps, hw=hw))
    return out


def measure_overlap(mesh=None, grid=None, reps: int = 5,
                    hw=None) -> Dict[str, float]:
    """Measure the schedule engine's *achieved* comm/compute overlap.

    For each multi-step algorithm the mesh admits, times the same
    multiply at ``pipeline_depth=1`` (serial) and ``pipeline_depth=2``
    (double-buffered) and converts the saving into an efficiency in
    [0, 1] against the model's predicted communication time:

        overlap_<algo> = (t_serial - t_pipelined) / comm_s_model

    This is the calibration source for the cost model's per-algorithm
    overlap discount (``HardwareModel.overlap_*``) — measured, not
    assumed, so a backend where XLA cannot hide collectives (e.g. the
    CPU interpret-mode container) calibrates to ~0 and the planner
    predicts serial behaviour.  ``overlap_ts`` reuses the Cannon value:
    the ts_* operand prefetch hides behind the same dot issue
    mechanism, but a single-step schedule gives nothing to difference.
    """
    import time

    import jax
    import jax.numpy as jnp

    from repro.core.multiply import distributed_matmul

    out: Dict[str, float] = {}
    if mesh is None or grid is None or mesh.devices.size <= 1:
        return out
    if hw is None:
        hw = get_hardware_model()

    def best_of(fn, *args):
        jax.block_until_ready(fn(*args))
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            best = min(best, time.perf_counter() - t0)
        return best

    from jax.sharding import NamedSharding, PartitionSpec as P

    rng = np.random.RandomState(0)
    pr, pc = grid.grid_shape(mesh)
    c_stack = grid.stack_size(mesh)

    def timed_pair(algo, m, k, n, **kw):
        a = jnp.asarray(rng.randn(m, k).astype(np.float32))
        b = jnp.asarray(rng.randn(k, n).astype(np.float32))
        sh = NamedSharding(mesh, P(grid.row_axis, grid.col_axis))
        a, b = jax.device_put(a, sh), jax.device_put(b, sh)
        fns = [jax.jit(lambda x, y, d=d: distributed_matmul(
            x, y, mesh=mesh, grid=grid, algorithm=algo, densify=True,
            pipeline_depth=d, **kw)) for d in (1, 2)]
        return best_of(fns[0], a, b), best_of(fns[1], a, b)

    def overlap_eff(t1, t2, comm_model_s):
        # measurability gate: when the model says communication is under
        # 10% of the serial runtime, a depth-1 vs depth-2 difference is
        # dominated by timing jitter and the quotient saved/comm would
        # amplify noise into a bogus efficiency (the CPU interpret-mode
        # backend lands here: compute dwarfs modelled comm, and it truly
        # cannot hide collectives — 0 is the honest answer).  Otherwise
        # a saving inside the 5%-of-t1 jitter band still calibrates to 0.
        saved = t1 - t2
        if comm_model_s < 0.1 * t1 or saved < 0.05 * t1:
            saved = 0.0
        return float(min(max(saved / comm_model_s, 0.0), 1.0))

    e = 4  # f32 operands
    targets = []
    if pr == pc:
        side = 128 * pr
        ml = side // pr
        comm = pr * 2 * ml * ml * e            # pg shifts of (a, b) chunks
        targets.append(("overlap_cannon", "cannon", side, comm, {}))
    side_s = 128 * max(pr, pc)
    mls, nls = side_s // pr, side_s // pc
    import math as _math

    n_panels = pc if pr == pc else _math.lcm(pr, pc)
    kls = side_s // n_panels
    comm_s_bytes = 2 * n_panels * (mls * kls + kls * nls) * e
    targets.append(("overlap_summa", "summa", side_s, comm_s_bytes, {}))

    for key, algo, side, comm_bytes, kw in targets:
        try:
            t1, t2 = timed_pair(algo, side, side, side, **kw)
        except Exception:
            continue
        comm_model_s = comm_bytes / hw.bytes_per_s
        if comm_model_s <= 0:
            continue
        out[key] = overlap_eff(t1, t2, comm_model_s)

    if "overlap_cannon" in out:
        # same ppermute pipeline, 1/c of the steps — reuse unless a
        # stack-axis mesh is available to measure directly
        out.setdefault("overlap_cannon25d", out["overlap_cannon"])
        out.setdefault("overlap_ts", out["overlap_cannon"])
    if c_stack > 1 and pr == pc:
        try:
            side = 128 * pr
            t1, t2 = timed_pair("cannon25d", side, side, side)
            ml = side // pr
            comm = (pr // c_stack) * 2 * ml * ml * e
            out["overlap_cannon25d"] = overlap_eff(
                t1, t2, comm / hw.bytes_per_s)
        except Exception:
            pass
    return out


def get_hardware_model(path: Optional[str] = None,
                       bench_dir: Optional[str] = None) -> HardwareModel:
    """Resolve defaults <- artifact fits <- calibration file (cached)."""
    global _CACHED
    if _CACHED is not None and path is None and bench_dir is None:
        return _CACHED
    merged = DEFAULT_HARDWARE.to_dict()
    merged.update(fit_from_artifacts(bench_dir or DEFAULT_BENCH_DIR))
    saved = _load_json(path or DEFAULT_CALIBRATION)
    if saved:
        merged.update({k: v for k, v in saved.items()
                       if k in merged and isinstance(v, (int, float))})
    hw = HardwareModel.from_dict(merged)
    if path is None and bench_dir is None:
        _CACHED = hw
    return hw


def invalidate_cache() -> None:
    global _CACHED
    _CACHED = None


def save_calibration(constants: Dict[str, float],
                     path: str = DEFAULT_CALIBRATION) -> str:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump({k: float(v) for k, v in constants.items()}, f, indent=1)
    invalidate_cache()
    return path


DEFAULT_PLAN_LOG = os.path.join("artifacts", "obs", "plan_outcomes.jsonl")


def drift_report(path: str = DEFAULT_PLAN_LOG, *,
                 threshold: float = 1.0, min_samples: int = 1) -> dict:
    """Check the telemetry layer's predicted-vs-actual plan-outcome log
    (``obs.record_plan_outcome`` rows, written by traced multiplies and
    ``benchmarks/bench_obs.py``) for calibration drift: algorithms
    whose median |relative error| exceeds ``threshold`` are flagged —
    the signal that this machine's constants need recalibration."""
    from repro.obs import read_jsonl
    from repro.obs.scoreboard import check_drift

    records = read_jsonl(path)
    result = check_drift(records, threshold=threshold,
                         min_samples=min_samples)
    result["path"] = path
    result["n_records"] = len(records)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--bench-dir", default=DEFAULT_BENCH_DIR)
    ap.add_argument("--out", default=DEFAULT_CALIBRATION)
    ap.add_argument("--micro", action="store_true",
                    help="also measure constants live (dense dot, fused "
                         "executor; single-device only from this CLI)")
    ap.add_argument("--check-drift", action="store_true",
                    help="instead of calibrating, read the predicted-vs-"
                         "actual plan-outcome log and warn when a per-"
                         "algorithm median |rel err| exceeds the "
                         "threshold")
    ap.add_argument("--drift-log", default=DEFAULT_PLAN_LOG,
                    help="plan-outcome JSONL (obs.enable(log_dir=...))")
    ap.add_argument("--drift-threshold", type=float, default=1.0,
                    help="median |predicted-measured|/measured per "
                         "algorithm above which drift is flagged")
    ap.add_argument("--strict", action="store_true",
                    help="with --check-drift: exit nonzero when drift "
                         "is flagged (or the log is missing/empty)")
    args = ap.parse_args()

    if args.check_drift:
        from repro.obs.scoreboard import render_scoreboard

        result = drift_report(args.drift_log,
                              threshold=args.drift_threshold)
        if not result["n_records"]:
            print(f"no plan outcomes at {args.drift_log} — run a traced "
                  f"multiply (obs.enable(log_dir=...)) or "
                  f"benchmarks/bench_obs.py first")
            if args.strict:
                raise SystemExit(1)
            return
        print(render_scoreboard(result["scoreboard"]))
        for algo, err in sorted(result["flagged"].items()):
            print(f"WARNING: {algo}: median |rel err| {err:.2f} exceeds "
                  f"drift threshold {args.drift_threshold:.2f} — "
                  f"recalibrate (python -m repro.planner.calibrate)")
        if result["ok"]:
            print(f"calibration drift OK ({result['n_records']} outcomes, "
                  f"threshold {args.drift_threshold:.2f})")
        elif args.strict:
            raise SystemExit(
                f"calibration drift: {sorted(result['flagged'])}")
        return

    constants = fit_from_artifacts(args.bench_dir)
    if args.micro:
        constants.update(micro_calibrate())
    path = save_calibration(constants, args.out)
    hw = get_hardware_model(path, args.bench_dir)
    print("fitted constants:")
    for k, v in hw.to_dict().items():
        src = ("calibrated" if k in constants else "default")
        print(f"  {k:20s} {v:12.4g}  [{src}]")
    print("wrote ->", path)


if __name__ == "__main__":
    main()
