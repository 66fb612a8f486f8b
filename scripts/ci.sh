#!/usr/bin/env bash
# Tier-1 gate (ROADMAP.md): the full suite must COLLECT cleanly and pass.
# Collection failures (missing optional deps, moved jax APIs) broke the
# seed suite once — this script exists so they can't land again.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

# collection must produce zero errors even where optional deps are absent
python -m pytest -q --collect-only >/dev/null

python -m pytest -x -q

# occupancy-aware stacks: the sparse dispatch win is tracked in the
# bench trajectory (artifacts/bench/sparse_smoke.json) and gated —
# --check fails the build if dispatch time stops falling with occupancy
# (also sweeps the executor's size-bin cap: padding must not grow with
# a larger cap)
python benchmarks/bench_sparse.py --smoke --check

# rank-exact execution (ISSUE 9): banded/block-diagonal/power-law
# patterns on a 2x2 mesh (artifacts/bench/sparse_patterns.json) —
# --check fails the build unless rank-exact products are bitwise equal
# to the union plan's, banded executed-triples-per-rank shrink >= 1.5x
# vs union, and the dense uniform-fill collapse adds no dispatch
# regression beyond jitter
python benchmarks/bench_sparse.py --patterns --smoke --check

# norm-based on-the-fly filtering (repro.sparsity): eps sweep +
# McWeeny purification trace (artifacts/bench/filter_smoke.json) —
# --check fails the build if retained triples stop falling with eps,
# if the 5%-retention dispatch is slower than the unfiltered one
# beyond the jitter floor, or if the purification occupancy stops
# decaying after its peak
python benchmarks/bench_filter.py --smoke --check

# multiply planner: recalibrates the cost model on this machine, sweeps
# square/tall/skinny x occupancy fills, and gates planner regret — the
# auto plan must be within 10% (+1ms interpret-mode jitter floor) of
# the best fixed (algorithm, local-path) choice at every sweep point
# (artifacts/bench/planner_smoke.json)
python benchmarks/bench_planner.py --smoke --check

# schedule engine: pipeline_depth 1 vs 2 for cannon/summa/cannon25d —
# the double-buffered driver must never lose to the serial one beyond
# the jitter floor, and the measured per-algorithm overlap constants
# feed the planner calibration (artifacts/bench/overlap_smoke.json)
python benchmarks/bench_overlap.py --smoke --check

# batched multiply service: fused one-dispatch batches vs the looped
# per-request baseline (artifacts/bench/batched_smoke.json) — --check
# fails the build unless the fused path clears 2x looped requests/s on
# >= 16 small same-geometry requests (results cross-checked bitwise)
python benchmarks/bench_batched.py --smoke --check

# ABFT self-verifying multiply (repro.robustness): verified-vs-plain
# overhead on the pinned config plus an injected-corruption sweep
# (artifacts/bench/abft_smoke.json) — --check fails the build unless
# verify="checksum" costs <= 25% wall-clock, every injected corruption
# is detected, localized to the exact block, and repaired to the
# bitwise-clean product, with zero false positives on clean and
# eps-filtered runs
python benchmarks/bench_abft.py --smoke --check

# chaos gate: the full injection matrix ({cannon,summa} x {dense,5%}
# x {bitflip,nan,scale}) on 1x1 and 2x2 meshes via the CLI
# (artifacts/bench/chaos_smoke.json) — nonzero exit unless every cell
# passes
PYTHONPATH=src python -m repro.robustness.chaos --report

# telemetry (repro.obs): tracing-on overhead <= 5% (or inside the
# baseline's own jitter spread), a multiply under a jax.profiler
# session leaves dbcsr.multiply > plan, stacks, dispatch, finish nested
# in the profiler's trace with the JAX runtime's counters on the root
# and dispatch, and the pinned algorithm sweep leaves a finite
# predicted-vs-actual scoreboard row per algorithm
# (artifacts/bench/obs_smoke.json)
python benchmarks/bench_obs.py --smoke --check

# tensor contractions (repro.tensor): the planner's matricization
# choice must be within 10% (+1 ms jitter floor) of the best fixed
# layout over square/tall/skinny contraction geometries, and the
# blocked executor dispatch built from the LOWERED N-d masks must get
# monotonically cheaper — fewer retained triples AND no slower — as
# tensor fill falls 100/50/20/5% (artifacts/bench/tensor_smoke.json)
python benchmarks/bench_tensor.py --smoke --check

# planner drift: compare the sweep's predicted-vs-measured log
# (artifacts/obs/plan_outcomes.jsonl, written by bench_obs) against the
# calibration — advisory here (no --strict): interpret-mode hosts run
# far from the calibrated model, so the scoreboard is printed for the
# trajectory rather than gated
python -m repro.planner.calibrate --check-drift || true
