"""Telemetry layer: tracing overhead + profiler-span + scoreboard gates.

The observability contract (repro.obs) has three measurable halves,
and this bench gates all of them in CI:

  overhead    a TRACED multiply (span records, synchronised dispatch,
              plan-outcome logging) vs the identical untraced one on the
              pinned deterministic config — tracing must cost <= 5% (or
              fall inside an absolute jitter floor; the disabled-by-
              default path is separately bitwise-gated in
              tests/test_obs.py)
  spans       one ``dbcsr.multiply`` under a ``jax.profiler`` session
              must leave ``dbcsr.multiply`` > ``dbcsr.plan``,
              ``dbcsr.stacks``, ``dbcsr.dispatch``, ``dbcsr.finish`` in
              the profiler's trace, in that order and inside the root,
              with the JAX runtime's counters on the root and dispatch
  scoreboard  a pinned algorithm sweep must leave one
              predicted-vs-actual row per executed algorithm, each
              with a finite signed relative error — the input
              ``planner.calibrate --check-drift`` consumes

    PYTHONPATH=src python -m benchmarks.bench_obs [--smoke] [--check]

``--smoke`` shrinks geometry/reps and writes
artifacts/bench/obs_smoke.json (scripts/ci.sh runs it with --check);
the full run writes artifacts/bench/obs.json.
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=1")

import argparse
import json
import time

import numpy as np
import jax

from repro import obs
from repro.compat import make_mesh
from repro.core import dbcsr

# pinned deterministic config: traced-vs-untraced is the IDENTICAL
# execution path, so the delta is pure telemetry cost
EXEC_KW = dict(algorithm="cannon", densify=False, local_kernel="ref",
               pipeline_depth=1)

OVERHEAD_GATE = 0.05          # traced <= 5% over untraced ...
OVERHEAD_ABS_FLOOR_S = 2e-3   # ... or within the host-timing jitter floor
PHASES = ["dbcsr.plan", "dbcsr.stacks", "dbcsr.dispatch", "dbcsr.finish"]
COUNTERS = ("lowerings", "traces", "compiles", "cache_hits", "lower_s",
            "compile_s", "lowered")
SWEEP_ALGOS = ("cannon", "summa", "ts_k")


def bench_overhead(mesh, geometry, block, reps, rng):
    """Interleaved best-of-``reps`` traced vs untraced wall time.

    Eager shard_map dispatch on the host backend has run-to-run jitter
    far above the telemetry cost, so the two paths are timed in
    ALTERNATION (machine-state drift hits both equally) and the gate
    allows the delta to fall inside the baseline's own observed spread
    — the untraced path disagreeing with itself by more than the
    traced-vs-untraced delta means no measurable overhead.
    """
    m, k, n = geometry
    a = dbcsr.create(rng.randn(m, k).astype(np.float32), mesh=mesh,
                     block_size=block)
    b = dbcsr.create(rng.randn(k, n).astype(np.float32), mesh=mesh,
                     block_size=block)
    kw = dict(mesh=mesh, **EXEC_KW)

    def run_once():
        c = dbcsr.multiply(a, b, **kw)
        jax.block_until_ready(c.data)

    def timed():
        t0 = time.perf_counter()
        run_once()
        return time.perf_counter() - t0

    obs.disable()
    run_once()                      # compile before timing either path
    plain, traced = [], []
    for _ in range(reps):
        obs.disable()
        plain.append(timed())
        obs.enable()                # in-memory tracer, no log files
        traced.append(timed())
    obs.disable()

    t_plain, t_traced = min(plain), min(traced)
    jitter = max(plain) - min(plain)
    overhead = (t_traced - t_plain) / t_plain
    ok = (overhead <= OVERHEAD_GATE
          or (t_traced - t_plain) <= max(OVERHEAD_ABS_FLOOR_S, jitter))
    row = {
        "geometry": list(geometry), "block": block, "reps": reps,
        "untraced_s": t_plain, "traced_s": t_traced,
        "untraced_all_s": plain, "traced_all_s": traced,
        "overhead_frac": overhead, "gate": OVERHEAD_GATE,
        "abs_floor_s": OVERHEAD_ABS_FLOOR_S, "jitter_s": jitter, "ok": ok,
    }
    print(f"overhead: {m}x{k}x{n} block {block}  "
          f"untraced {t_plain*1e3:8.2f} ms  traced {t_traced*1e3:8.2f} ms  "
          f"{overhead*100:+5.1f}%  (gate {OVERHEAD_GATE*100:.0f}% or "
          f"jitter floor {max(OVERHEAD_ABS_FLOOR_S, jitter)*1e3:.1f} ms)")
    return row


def bench_profile_spans(mesh, geometry, block, rng, out_dir):
    """One profiled multiply -> the phase spans nested on one clock."""
    m, k, n = geometry
    a = dbcsr.create(rng.randn(m, k).astype(np.float32), mesh=mesh,
                     block_size=block)
    b = dbcsr.create(rng.randn(k, n).astype(np.float32), mesh=mesh,
                     block_size=block)
    trace_dir = os.path.join(out_dir, "obs_profile")
    with jax.profiler.trace(trace_dir):
        c = dbcsr.multiply(a, b, mesh=mesh, **EXEC_KW)
        jax.block_until_ready(c.data)
    spans = obs.profile_spans(trace_dir)

    roots = [s for s in spans if s.parent_id is None]
    nested_ok = len(roots) == 1 and roots[0].name == "dbcsr.multiply"
    row = {"trace_dir": trace_dir, "n_spans": len(spans),
           "n_roots": len(roots)}
    if nested_ok:
        root = roots[0]
        kids = sorted((s for s in spans if s.parent_id == root.span_id),
                      key=lambda s: s.t0)
        disp = [s for s in kids if s.name == "dbcsr.dispatch"]
        nested_ok = ([s.name for s in kids] == PHASES
                     and sum(s.dur for s in kids) <= root.dur
                     and all(key in s.attrs for key in COUNTERS
                             for s in [root] + disp))
        row.update(root_s=root.dur, phases_s={s.name: s.dur for s in kids},
                   root_counters={key: root.attrs.get(key)
                                  for key in COUNTERS})
    row["nested_ok"] = nested_ok
    print(f"spans:    {len(spans)} dbcsr.* spans -> {trace_dir}  "
          f"nested with counters: {nested_ok}")
    print(obs.render_timeline(spans))
    return row


def bench_scoreboard(mesh, geometry, block, rng, log_dir):
    """Pinned algorithm sweep -> one scoreboard row per algorithm."""
    m, k, n = geometry
    a = dbcsr.create(rng.randn(m, k).astype(np.float32), mesh=mesh,
                     block_size=block)
    b = dbcsr.create(rng.randn(k, n).astype(np.float32), mesh=mesh,
                     block_size=block)
    obs.clear_plan_outcomes()
    obs.enable(log_dir=log_dir)
    for algo in SWEEP_ALGOS:
        kw = dict(EXEC_KW, algorithm=algo)
        c = dbcsr.multiply(a, b, mesh=mesh, **kw)
        jax.block_until_ready(c.data)
    obs.disable()
    outcomes = obs.plan_outcomes()
    sb = obs.planner_scoreboard(outcomes)
    print(obs.render_scoreboard(sb))
    complete = all(
        algo in sb and sb[algo]["n"] >= 1
        and np.isfinite(sb[algo]["rel_err_median"])
        for algo in SWEEP_ALGOS)
    return {"algorithms": list(SWEEP_ALGOS), "n_outcomes": len(outcomes),
            "scoreboard": sb, "complete": complete,
            "plan_log": os.path.join(log_dir, obs.PLAN_OUTCOMES_LOG)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small geometry, few reps -> obs_smoke.json")
    ap.add_argument("--check", action="store_true",
                    help="exit nonzero unless tracing overhead <= 5%, a "
                         "profiled multiply leaves its phase spans nested "
                         "with the runtime's counters, and the sweep "
                         "scoreboard has a finite predicted-vs-actual row "
                         "per algorithm (CI gate)")
    ap.add_argument("--out", default="artifacts/bench")
    ap.add_argument("--obs-dir", default="artifacts/obs",
                    help="log dir for the sweep's plan_outcomes.jsonl "
                         "(what calibrate --check-drift reads)")
    args = ap.parse_args()

    if args.smoke:
        geometry, block, reps = (256, 256, 256), 32, 3
    else:
        geometry, block, reps = (512, 512, 512), 32, 5

    mesh = make_mesh((1, 1), ("data", "model"))
    rng = np.random.RandomState(0)
    os.makedirs(args.out, exist_ok=True)
    os.makedirs(args.obs_dir, exist_ok=True)

    overhead = bench_overhead(mesh, geometry, block, reps, rng)
    spans = bench_profile_spans(mesh, geometry, block, rng, args.out)
    scoreboard = bench_scoreboard(mesh, geometry, block, rng, args.obs_dir)

    gates = {
        "overhead_ok": bool(overhead["ok"]),
        "spans_nested": bool(spans["nested_ok"]),
        "scoreboard_complete": bool(scoreboard["complete"]),
    }
    result = {
        "exec_kw": {k: str(v) for k, v in EXEC_KW.items()},
        "overhead": overhead,
        "spans": spans,
        "scoreboard": scoreboard,
        "gates": gates,
    }
    name = "obs_smoke.json" if args.smoke else "obs.json"
    path = os.path.join(args.out, name)
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    print("gates:", gates)
    print("wrote ->", path)
    if args.check and not all(gates.values()):
        raise SystemExit(f"telemetry gate failed: "
                         f"{[k for k, v in gates.items() if not v]}")


if __name__ == "__main__":
    main()
