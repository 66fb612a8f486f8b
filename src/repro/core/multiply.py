"""Top-level distributed multiply dispatcher.

Implements DBCSR's algorithm selection (paper section II): with
``algorithm="auto"`` (the default) the cost-model planner
(repro.planner.plan_multiply) evaluates every feasible candidate —
Cannon / SUMMA / 2.5D Cannon / the tall-and-skinny variants, each with
a densified or blocked local path — against calibrated hardware
constants and picks the cheapest, which is the paper's driver
behaviour (the "different sizes and shapes" headline).  A fixed
``algorithm=`` string bypasses the planner entirely.  The local
multiply is either 'densified' (one big GEMM — the paper's section III
optimization) or 'blocked' (stack-of-small-GEMMs via the smm kernel);
``densify=None`` leaves that choice to the planner too.

Every algorithm executes through the unified schedule engine
(core/schedule.py): the algorithm module emits a step schedule (comm
op, per-step mask slice, local multiply geometry) and the pipelined
driver runs it with software double-buffering — ``pipeline_depth=2``
(default) issues the ppermute / panel broadcast for step t+1 while
step t's stacks execute, ``pipeline_depth=1`` is strictly serial with
bit-identical output.

Occupancy threading (blocked path): ``a_mask`` / ``b_mask`` are the
*global* block-occupancy masks of the operands (host-side numpy bool).
Each algorithm states once which block ranges every mesh rank holds at
every data-exchange step — each cannon shift, each summa panel — as
its step map (``cannon_step_map`` / ``summa_step_map`` /
``ts_step_map``), and the views of core/steps.py reduce that map over
the masks (and norms).  The union view unions every rank's slice per
step (shard_map traces ONE program for all devices, so a shared plan
must cover every rank's present triples; the union is the tightest
SPMD-uniform one).  Plans are memoized per mask content fingerprint
(core/engine.py), and a step with no retained triple on any rank skips
its ``execute_plan`` — and for summa, the panel broadcast — entirely.
The densified path ignores the masks and builds no map: absent blocks
are stored as zeros, so one big GEMM is already correct.

Rank-exact execution (default for masked/filtered blocked multi-rank
paths; ``rank_exact=False`` plans from the union view instead): the
rank view gives each rank's EXACT mask/norm slices, and the engine
stacks the per-rank plans into one host-constant slab every rank
indexes with ``jax.lax.axis_index`` inside shard_map
(core/engine.rank_stack_executor) — still one traced program, but a
rank executes only its own retained triples, never the union's.
Per-step emptiness stays host-static as the all-ranks-empty
intersection (identical to union emptiness: the max norm product over
ranks clears eps iff some rank retains a triple).  Steps whose
per-rank slices are content-identical (dense padding, uniform fill)
collapse to the shared union executor, bitwise-identical to the union
path.  On top of that, the planner's costed permutation pass
(repro.sparsity.balance) can permute block rows/cols of A/B before the
multiply and invert the permutation on C, flattening per-rank load
imbalance when the predicted compute saved exceeds the shuffle's cost
(DBCSR's randomized-distribution trick, arXiv:1910.04796 sec. 2).
"""
from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs

from .blocking import GridSpec
from .cannon import build_cannon_schedule, cannon_matmul, cannon_step_map
from .cannon25d import build_cannon25d_schedule, cannon25d_matmul
from .densify import blocked_local_matmul, densified_local_matmul
from .engine import rank_stack_executor
from .schedule import (Schedule, resolve_pipeline_depth, schedule_step_meta,
                       skew_panels)
from .stacks import normalize_block_masks
from .steps import StepMap, empty_steps, rank_view, union_view
from .summa import (build_summa_gather_schedule, build_summa_schedule,
                    summa_matmul, summa_n_panels, summa_step_map)
from .tall_skinny import (build_ts_schedule, tall_skinny_matmul, ts_specs,
                          ts_step_map)

__all__ = ["distributed_matmul"]


def _block_masks(
    m: int, k: int, n: int,
    block_m: int, block_k: int, block_n: int,
    a_mask: Optional[np.ndarray], b_mask: Optional[np.ndarray],
) -> Tuple[np.ndarray, np.ndarray]:
    """Normalise the *global* occupancy masks; a missing mask means the
    operand is dense (all blocks present)."""
    return normalize_block_masks(m // block_m, k // block_k, n // block_n,
                                 a_mask, b_mask)


def _global_occupancy(
    m: int, k: int, n: int,
    block_m: int, block_k: int, block_n: int,
    a_mask: Optional[np.ndarray], b_mask: Optional[np.ndarray],
    a_norms: Optional[np.ndarray] = None,
    b_norms: Optional[np.ndarray] = None,
    filter_eps: Optional[float] = None,
) -> float:
    """Retained-triple fraction of the global dense triple grid — the
    occupancy the planner discounts blocked-path flops by.  With block
    norms and a ``filter_eps`` this is the NORM-PREDICTED fraction
    (mask-present triples whose norm product clears eps), so the
    planner's blocked-path discount reflects the on-the-fly filter, not
    just binary occupancy.  An empty product returns 0.0, which the
    planner short-circuits to a trivial plan — including the case where
    eps filtering empties a product whose binary masks are non-empty
    (the same contract as ``steps.masks_empty`` per step: the blocked
    cost model must never divide by zero occupancy)."""
    filtering = filter_eps is not None and (
        a_norms is not None or b_norms is not None)
    if a_mask is None and b_mask is None and not filtering:
        return 1.0
    from .engine import _mask_fill

    return _mask_fill(m // block_m, k // block_k, n // block_n,
                      a_mask, b_mask, None,
                      a_norms, b_norms, None, filter_eps)


def _collect_executor_stats(lm, densify: bool) -> Optional[dict]:
    """Executed-plan stack statistics for plan observability
    (dbcsr.multiply exposes these as ``last_plan.executor_stats``)."""
    if densify:
        return None
    if getattr(lm, "stepwise", False):
        ex = [f.executor_plan for f in lm.step_executors if f is not None]
        n_entries = sum(p.n_entries for p in ex)
        n_dense = sum(p.n_dense_triples for p in ex)
        n_padding = sum(p.n_padding for p in ex)
        n_padding_unbinned = sum(p.n_padding_unbinned for p in ex)
        n_unfiltered = sum(
            p.n_entries if p.n_unfiltered_entries is None
            else p.n_unfiltered_entries for p in ex)
        stats = {
            "n_steps": len(lm.step_executors),
            "n_empty_steps": len(lm.empty_steps),
            "n_entries": n_entries,
            "n_dense_triples": n_dense,
            "n_skipped_triples": n_dense - n_entries,
            "occupancy": n_entries / n_dense if n_dense else 1.0,
            "n_padding": n_padding,
            "n_padding_unbinned": n_padding_unbinned,
            "padding_triples_saved": n_padding_unbinned - n_padding,
            # on-the-fly filter accounting (repro.sparsity): triples the
            # binary masks admitted but the norm-product bound dropped
            "n_unfiltered_triples": n_unfiltered,
            "n_norm_filtered_triples": n_unfiltered - n_entries,
        }
        totals = _rank_totals(lm)
        if totals is not None:
            # rank-exact accounting: the busiest rank's total bounds
            # wall time; mean is the flattened-load floor rebalancing
            # aims for (n_entries above already sums per-step maxima)
            stats.update(
                rank_exact=True,
                rank_entries=[int(x) for x in totals],
                max_rank_entries=int(totals.max()),
                mean_rank_entries=float(totals.mean()),
                rank_imbalance=_rank_imbalance_of(totals),
            )
        return stats
    plan = getattr(lm, "executor_plan", None)
    return None if plan is None else plan.stats()


def _stepwise_lm(steps: Sequence[Sequence[dict]], executor,
                 filter_eps: Optional[float] = None):
    """A stepwise local multiply: ``steps[t]`` lists step t's mask/norm
    kwargs (the one union every rank shares, one per rank, or one per
    fused group) and ``executor(steps[t])`` builds that step's stack
    executor.  A step with no retained triple in any entry
    (``steps.empty_steps``) carries none; the schedule driver skips it
    host-side."""
    empty = empty_steps(steps, filter_eps)
    fns = [None if t in empty else executor(kws)
           for t, kws in enumerate(steps)]

    def lm(a_loc: jax.Array, b_loc: jax.Array, step: int = 0):
        f = fns[step]
        return None if f is None else f(a_loc, b_loc)

    lm.stepwise = True
    lm.empty_steps = empty
    lm.step_executors = fns
    return lm


# ---------------------------------------------------------------------------
# rank-exact execution (ISSUE 9): per-rank plan slabs + costed rebalance
# ---------------------------------------------------------------------------


def _rank_kwargs_equal(rank_kwargs: List[dict]) -> bool:
    """True when every rank's step kwargs are content-identical — the
    dense / uniform-fill collapse: one shared plan IS every rank's
    exact plan, so the union executor (today's trace) already executes
    rank-exactly and we keep its bitwise-identical program."""
    first = rank_kwargs[0]
    keys = set(first)
    for rk in rank_kwargs[1:]:
        if set(rk) != keys:
            return False
        for key in keys:
            u, v = first[key], rk[key]
            if u is None or v is None:
                if u is not v:
                    return False
            elif u.shape != v.shape or not np.array_equal(u, v):
                return False
    return True


def _rank_index_fn(algorithm: str, grid: GridSpec, mesh):
    """Zero-arg closure returning this rank's traced flat index inside
    the shard_map body (``jax.lax.axis_index`` over the mesh axes), in
    the step maps' rank order (core/steps.py): cannon ``i*pg + j``;
    cannon25d / stacked tall-skinny stack-major ``(s*pr + i)*pc + j``;
    summa / flat tall-skinny ``i*pc + j``."""
    pr, pc = grid.grid_shape(mesh)
    row, col = grid.row_axis, grid.col_axis
    stacked = (algorithm == "cannon25d"
               or (algorithm.startswith("ts_")
                   and grid.stack_axis is not None))
    if stacked:
        stack = grid.stack_axis
        return lambda: ((jax.lax.axis_index(stack) * pr
                         + jax.lax.axis_index(row)) * pc
                        + jax.lax.axis_index(col))
    return lambda: jax.lax.axis_index(row) * pc + jax.lax.axis_index(col)


def _summa_panels(pr: int, pc: int, kw: dict) -> int:
    """SUMMA's panel count: the all-gather broadcast is one panel of
    full K, the psum broadcast ``summa_n_panels``."""
    return 1 if kw.get("bcast") == "gather" else summa_n_panels(pr, pc)


def _step_map(algorithm: str, grid: GridSpec, mesh, block_grid: tuple,
              kw: dict) -> StepMap:
    """``algorithm``'s step map (core/steps.py) on ``mesh`` over the
    (nbr, nbk, nbc) ``block_grid``; ``kw`` carries ``bcast``."""
    pr, pc = grid.grid_shape(mesh)
    if algorithm in ("cannon", "cannon25d"):
        return cannon_step_map(
            *block_grid, pr,
            grid.stack_size(mesh) if algorithm == "cannon25d" else 1)
    if algorithm == "summa":
        return summa_step_map(*block_grid, pr, pc, _summa_panels(pr, pc, kw))
    return ts_step_map(algorithm, *block_grid,
                       pr * pc * grid.stack_size(mesh))


def _blocked_steps_lm(ml: int, kl: int, nl: int, steps: List[List[dict]],
                      *, rank_index_fn=None,
                      filter_eps: Optional[float] = None, **blocked_kw):
    """The blocked local multiply of a masked schedule (``_stepwise_lm``).
    A step whose entries are content-identical — the union view's one
    entry, or every rank's slice of a uniform fill — runs the shared
    ``blocked_local_matmul``, bitwise the union path; any other step
    runs one stacked per-rank slab that each rank indexes with
    ``rank_index_fn`` (core/engine.rank_stack_executor; a union view
    never needs it)."""
    def executor(kws):
        if _rank_kwargs_equal(kws):
            return blocked_local_matmul(ml, kl, nl, **kws[0],
                                        filter_eps=filter_eps, **blocked_kw)
        return rank_stack_executor(ml, kl, nl, rank_masks=kws,
                                   rank_index_fn=rank_index_fn,
                                   filter_eps=filter_eps, **blocked_kw)

    return _stepwise_lm(steps, executor, filter_eps)


def _rank_totals(lm) -> Optional[np.ndarray]:
    """Per-rank executed-entry totals over the whole multiply (summed
    across steps; collapsed/union steps charge every rank the shared
    plan's entries).  None when no step executed rank-exactly."""
    fns = getattr(lm, "step_executors", [lm])
    plans = [getattr(f, "executor_plan", None)
             for f in fns if f is not None]
    ranked = [p for p in plans if hasattr(p, "rank_entries")]
    if not ranked:
        return None
    totals = np.zeros(ranked[0].n_ranks, dtype=np.int64)
    for p in plans:
        if p is None:
            continue
        if hasattr(p, "rank_entries"):
            totals += np.asarray(p.rank_entries, dtype=np.int64)
        else:
            totals += int(p.n_entries)
    return totals


def _rank_imbalance_of(totals: Optional[np.ndarray]) -> Optional[float]:
    if totals is None:
        return None
    mean = float(totals.mean())
    return float(totals.max()) / mean if mean > 0 else 1.0


# ---------------------------------------------------------------------------
# schedule observability: per-step comm/compute split
# ---------------------------------------------------------------------------


def _build_meta_schedule(algorithm: str, *, grid, mesh, local_shape,
                         itemsize: int, empty_steps, reduce_kw: dict):
    """Rebuild the executed schedule purely for its host-side metadata
    (building a Schedule traces nothing — see core/schedule.py)."""
    pr, pc = grid.grid_shape(mesh)
    if algorithm == "cannon":
        return build_cannon_schedule(
            pr, row_axis=grid.row_axis, col_axis=grid.col_axis,
            empty_steps=empty_steps, local_shape=local_shape,
            itemsize=itemsize)
    if algorithm == "cannon25d":
        return build_cannon25d_schedule(
            pr, grid.stack_size(mesh), row_axis=grid.row_axis,
            col_axis=grid.col_axis, stack_axis=grid.stack_axis,
            reduce=reduce_kw.get("reduce", "all_reduce"),
            empty_steps=empty_steps, local_shape=local_shape,
            itemsize=itemsize)
    if algorithm == "summa":
        if reduce_kw.get("bcast") == "gather":
            return build_summa_gather_schedule(
                grid.row_axis, grid.col_axis, local_shape=local_shape,
                itemsize=itemsize)
        return build_summa_schedule(
            pr, pc, row_axis=grid.row_axis, col_axis=grid.col_axis,
            empty_steps=empty_steps, local_shape=local_shape,
            itemsize=itemsize)
    axes = ((grid.row_axis, grid.col_axis) if grid.stack_axis is None
            else (grid.stack_axis, grid.row_axis, grid.col_axis))
    return build_ts_schedule(
        algorithm, axes, reduce=reduce_kw.get("reduce", "reduce_scatter"),
        local_shape=local_shape, itemsize=itemsize)


def _schedule_stats(algorithm: str, *, grid, mesh, local_shape, itemsize,
                    lm, densify: bool, pipeline_depth: int,
                    reduce_kw: dict, n_groups: int = 1) -> dict:
    """Per-step comm-vs-compute split of the executed schedule, priced
    with the calibrated hardware constants (host-side observability —
    attached to executed plans as ``schedule_stats``, its total comm
    bytes on the telemetry layer's dispatch span).  ``n_groups`` scales
    comm bytes and dense flops for the fused batched dispatch, whose
    every step moves/computes G same-geometry products at once."""
    from repro.planner.calibrate import get_hardware_model

    hw = get_hardware_model()
    empty = getattr(lm, "empty_steps", frozenset())
    sched = _build_meta_schedule(
        algorithm, grid=grid, mesh=mesh, local_shape=local_shape,
        itemsize=itemsize * n_groups, empty_steps=empty,
        reduce_kw=reduce_kw)
    meta = schedule_step_meta(sched)

    ml, kl, nl = local_shape
    dense_flops = 2.0 * ml * kl * nl * n_groups
    step_execs = getattr(lm, "step_executors", None)
    steps = []
    for t in range(meta["n_steps"]):
        comm_bytes = meta["step_comm_bytes"][t]
        plan = None
        if not densify and t not in empty:
            # stepwise executors carry .executor_plan (blocked path) or
            # .batched_plan (fused batched path); both expose
            # n_entries/block_* — enough to price the stack dispatch
            ex = step_execs[t] if step_execs is not None else lm
            plan = (getattr(ex, "executor_plan", None)
                    or getattr(ex, "batched_plan", None))
        if t in empty:
            flops = 0.0
            compute_s = 0.0
        elif plan is not None:
            flops = 2.0 * plan.n_entries * plan.block_m * plan.block_k \
                * plan.block_n
            compute_s = flops / hw.smm_flops_per_s \
                + plan.n_entries * hw.stack_entry_s
        else:
            flops = dense_flops
            compute_s = flops / hw.flops_per_s
        n_dense = getattr(plan, "n_dense_triples", None)
        ranked = plan is not None and hasattr(plan, "rank_entries")
        steps.append({
            "step": t,
            "skipped": t in empty,
            "comm_bytes": comm_bytes,
            "comm_s": comm_bytes / hw.bytes_per_s,
            "flops": flops,
            "compute_s": compute_s,
            "n_entries": None if plan is None else int(plan.n_entries),
            "occupancy": (plan.n_entries / n_dense
                          if plan is not None and n_dense else None),
            # rank-exact steps: the per-rank retained counts behind the
            # busiest-rank n_entries above (None on union/collapsed)
            "rank_entries": (list(map(int, plan.rank_entries))
                             if ranked else None),
            "rank_imbalance": (float(plan.rank_imbalance)
                               if ranked else None),
        })
    comm_s = sum(s["comm_s"] for s in steps)
    compute_s = sum(s["compute_s"] for s in steps)
    # at depth >= 2 the shift/broadcast feeding step t+1 hides behind
    # step t's compute: all but the first step's comm is overlappable
    overlappable = sum(s["comm_s"] for s in steps[:-1]) \
        if meta["algorithm"] in ("cannon", "cannon25d") \
        else sum(s["comm_s"] for s in steps[1:])
    overlap_bound_s = (min(overlappable, compute_s)
                       if pipeline_depth >= 2 and meta["n_steps"] > 1 else 0.0)
    return {
        **meta,
        "pipeline_depth": pipeline_depth,
        "steps": steps,
        "comm_s": comm_s,
        "compute_s": compute_s,
        "prologue_comm_s": meta["prologue_comm_bytes"] / hw.bytes_per_s,
        "epilogue_comm_s": meta["epilogue_comm_bytes"] / hw.bytes_per_s,
        "overlap_bound_s": overlap_bound_s,
    }


def _boundary_bytes(x, mesh, spec) -> Optional[int]:
    """The most bytes any device receives when ``x`` is laid out as
    ``spec`` on ``mesh`` at a ``shard_map``'s boundary: the elements of
    its shard that it does not already hold, times the itemsize.  None
    where ``x`` has no sharding to count from (a host array, a
    tracer)."""
    src = None if isinstance(x, jax.core.Tracer) else getattr(
        x, "sharding", None)
    if src is None:
        return None
    shape = tuple(x.shape)
    dst = NamedSharding(mesh, spec)
    if src.is_equivalent_to(dst, len(shape)):
        return 0
    held = src.devices_indices_map(shape)

    def extent(index, other=None):
        n = 1
        for dim, s, o in zip(shape, index, other or index):
            lo, hi, _ = s.indices(dim)
            olo, ohi, _ = o.indices(dim)
            n *= max(0, min(hi, ohi) - max(lo, olo))
        return n

    worst = 0
    for device, index in dst.devices_indices_map(shape).items():
        have = held.get(device)
        missing = extent(index) - (0 if have is None
                                   else extent(index, have))
        worst = max(worst, missing)
    return worst * jnp.dtype(x.dtype).itemsize


def _comm_count(algorithm: str, a, b, *, mesh, grid, local_shape,
                empty_steps=frozenset(), kw: dict,
                sched: Optional[Schedule] = None) -> dict:
    """The schedule's host-static accounting of one multiply:
    ``comm_steps``, its collective phases (prologue, steps and epilogue
    that move data, and the resharding of operands at the ``shard_map``
    boundary), and ``comm_bytes``, the most bytes any chip receives over
    them in the dtype the schedule hands its collectives, the operands'
    (a compiler may narrow them on the wire: XLA on the TPU sends bf16
    for a float32 product at ``Precision.DEFAULT``, half of this count).
    ``comm_bytes`` is left out where the
    boundary cannot be counted, never reported as 0 for a transfer that
    happens.  Built from shapes, shardings and the schedules' own byte
    counts alone: no pricing.  ``sched`` is the meta schedule
    (``_build_meta_schedule``) where the caller has built it."""
    if sched is None:
        sched = _build_meta_schedule(
            algorithm, grid=grid, mesh=mesh, local_shape=local_shape,
            itemsize=_itemsize(a, b), empty_steps=empty_steps,
            reduce_kw=kw)
    meta = schedule_step_meta(sched)
    phases = [meta["prologue_comm_bytes"], *meta["step_comm_bytes"],
              meta["epilogue_comm_bytes"]]
    if algorithm.startswith("ts_"):
        axes = ((grid.row_axis, grid.col_axis) if grid.stack_axis is None
                else (grid.stack_axis, grid.row_axis, grid.col_axis))
        specs = ts_specs(algorithm, axes,
                         kw.get("reduce", "reduce_scatter"))[0]
    else:
        specs = (P(grid.row_axis, grid.col_axis),) * 2
    boundary = [_boundary_bytes(x, mesh, spec)
                for x, spec in zip((a, b), specs)]
    out = {"comm_steps": sum(1 for p in phases if p) + any(boundary)}
    if None not in boundary:
        out["comm_bytes"] = int(sum(phases) + sum(boundary))
    return out


def _itemsize(a, b) -> int:
    return int(jnp.dtype(jnp.promote_types(a.dtype, b.dtype)).itemsize)


def _counted(algorithm: str, a, b, **kw) -> dict:
    """``_comm_count``, or nothing where it fails: the accounting must
    never break the multiply."""
    try:
        return _comm_count(algorithm, a, b, **kw)
    except Exception:
        return {}


# ---------------------------------------------------------------------------
# dispatch: the schedule call, and the densified path's cached programs
# ---------------------------------------------------------------------------


def _schedule_matmul(algorithm: str, a, b, *, mesh, grid, local_matmul,
                     precision, pipeline_depth: int, **kw):
    """C = A @ B through ``algorithm``'s schedule (core/schedule.py);
    ``kw`` carries the schedule's own options (``reduce``, ``bcast``)."""
    if algorithm == "cannon":
        return cannon_matmul(
            a, b, mesh=mesh, grid=grid, local_matmul=local_matmul,
            precision=precision, pipeline_depth=pipeline_depth, **kw)
    if algorithm == "cannon25d":
        return cannon25d_matmul(
            a, b, mesh=mesh, grid=grid, local_matmul=local_matmul,
            precision=precision, pipeline_depth=pipeline_depth, **kw)
    if algorithm in ("ts_k", "ts_m", "ts_n"):
        return tall_skinny_matmul(
            a, b, mesh=mesh, grid=grid, mode=algorithm,
            local_matmul=local_matmul, precision=precision,
            pipeline_depth=pipeline_depth, **kw)
    return summa_matmul(
        a, b, mesh=mesh, grid=grid, local_matmul=local_matmul,
        precision=precision, pipeline_depth=pipeline_depth, **kw)


_PROGRAM_CACHE_SIZE = 512   # as the planner's plan cache
# key -> (program, its _comm_count and skew_panels)
_programs: "collections.OrderedDict[tuple, Tuple[Callable, dict]]" = \
    collections.OrderedDict()
_programs_lock = threading.Lock()


def _operand_key(x) -> tuple:
    """What a compiled program depends on in an operand: its abstract
    value (shape, dtype, weak type) and, for a concrete array, its
    sharding and whether it is committed to it."""
    if isinstance(x, jax.core.Tracer):
        return jax.typeof(x), None, None
    return (jax.typeof(x), getattr(x, "sharding", None),
            getattr(x, "committed", None))


def _densified_program(algorithm: str, a, b, *, mesh, grid, depth: int,
                       precision, local_kernel: Optional[str],
                       local_shape: tuple,
                       kw: dict) -> Tuple[Callable, dict, bool]:
    """The densified dispatch as one ``jax.jit`` program ``(a, b) ->
    C``, its ``_comm_count`` with the schedule's ``skew_panels``, from
    one meta schedule (held beside it, so a warm call prices nothing),
    and whether it came from the cache (a hit: this key's
    program is compiled, so the call neither traces, lowers nor
    compiles).

    The key is the static configuration the program depends on
    (algorithm, mesh, grid, pipeline depth, precision, local kernel and
    the schedule's keyword options) and both operands' ``_operand_key``.
    The program builds its local multiply and its schedule call from
    the key alone, so the algorithms' ``shard_map`` is traced under
    ``jit`` once per key instead of run eagerly, one program per
    primitive, on every call.  Least recently used keys beyond
    ``_PROGRAM_CACHE_SIZE`` are dropped with their executables."""
    key = (algorithm, mesh, grid, depth, precision, local_kernel,
           tuple(sorted(kw.items())), _operand_key(a), _operand_key(b))
    with _programs_lock:
        entry = _programs.get(key)
        if entry is not None:
            _programs.move_to_end(key)
            return (*entry, True)
    lm = densified_local_matmul(precision, kernel=local_kernel)
    options = dict(key[6])

    def densified_dispatch(a, b):
        return _schedule_matmul(algorithm, a, b, mesh=mesh, grid=grid,
                                local_matmul=lm, precision=precision,
                                pipeline_depth=depth, **options)

    sched = _build_meta_schedule(
        algorithm, grid=grid, mesh=mesh, local_shape=local_shape,
        itemsize=_itemsize(a, b), empty_steps=frozenset(), reduce_kw=options)
    ml, _, nl = local_shape
    entry = (jax.jit(densified_dispatch),
             {**_counted(algorithm, a, b, mesh=mesh, grid=grid,
                         local_shape=local_shape, kw=options, sched=sched),
              "skew_panels": skew_panels(sched, lm, ml, nl, depth)})
    with _programs_lock:
        _programs[key] = entry
        while len(_programs) > _PROGRAM_CACHE_SIZE:
            _programs.popitem(last=False)
    return (*entry, False)


def _verified_result(verify, a, b, c, rerun, *, plan, block_m, block_k,
                     block_n, a_mask, b_mask, a_norms, b_norms, filter_eps,
                     verify_budget, _live: bool = False):
    """ABFT verification of a raw product (repro.robustness.abft):
    price the checksum overhead against the plan (``verify="auto"``),
    screen the operands with the finite tripwires, apply any installed
    chaos hook (test-only corruption — modelling a soft error between
    compute and verification), then verify / one-shot-repair.  Returns
    ``(c, verification_dict)``; the dict lands on the plan as
    ``plan.verification``."""
    from repro.planner.plan import decide_verify

    m, k = a.shape
    n = b.shape[1]
    itemsize = int(jnp.dtype(jnp.promote_types(a.dtype, b.dtype)).itemsize)
    pricing = decide_verify(plan, m, k, n,
                            blocks=(block_m, block_k, block_n),
                            itemsize=itemsize, budget=verify_budget)
    enabled = verify == "checksum" or (verify == "auto"
                                       and pricing["auto_enabled"])
    if plan is not None and getattr(plan, "trivial", False):
        enabled = False  # empty product: nothing executed to corrupt
    info = {"mode": verify, "enabled": enabled, **pricing, "report": None}
    if not enabled:
        return c, info
    from repro.robustness import abft, chaos, guards

    def _repair_rerun():
        # a detection re-executes the deterministic dispatch once; the
        # repair span makes that second dispatch visible in the trace
        with obs.maybe_span(_live, "repair", cat="repair"):
            return rerun()

    with obs.maybe_span(_live, "verify", cat="verify", mode=verify) as vsp:
        guards.assert_finite(a, "A")
        guards.assert_finite(b, "B")
        c = chaos.apply_result_hook(c)
        c, report = abft.verify_and_repair(
            a, b, c, recompute=_repair_rerun,
            block_m=block_m, block_k=block_k, block_n=block_n,
            a_mask=a_mask, b_mask=b_mask, a_norms=a_norms, b_norms=b_norms,
            filter_eps=filter_eps)
        vsp.set(detected=bool(report.detected),
                repaired=bool(report.repaired),
                n_flagged_blocks=len(report.flagged_blocks))
    info["report"] = report
    return jnp.asarray(c), info


def distributed_matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    mesh: jax.sharding.Mesh,
    grid: GridSpec = GridSpec(),
    algorithm: str = "auto",
    densify: Optional[bool] = None,
    block_m: int = 64,
    block_k: int = 64,
    block_n: int = 64,
    stack_size: Optional[int] = None,
    align: Optional[bool] = None,
    local_kernel: Optional[str] = None,
    a_mask: Optional[np.ndarray] = None,
    b_mask: Optional[np.ndarray] = None,
    a_norms: Optional[np.ndarray] = None,
    b_norms: Optional[np.ndarray] = None,
    filter_eps: Optional[float] = None,
    stack_bins: Optional[int] = None,
    rank_exact: Optional[bool] = None,
    rebalance: Optional[bool] = None,
    precision=jax.lax.Precision.DEFAULT,
    pipeline_depth: Optional[int] = None,
    double_buffer: Optional[bool] = None,
    verify: Optional[str] = None,
    verify_budget: Optional[float] = None,
    return_plan: bool = False,
    **kw,
) -> jax.Array:
    """C = A @ B on the mesh. ``algorithm``:

      auto         — cost-model planner (repro.planner.plan_multiply):
                     cheapest feasible (algorithm, local path) for this
                     (shape, occupancy, mesh)
      cannon       — Cannon's algorithm (square grids)
      cannon25d    — 2.5D Cannon over grid.stack_axis
      ts_k|ts_m|ts_n — tall-and-skinny variants
      summa        — the ScaLAPACK-PDGEMM-style baseline

    ``densify`` picks the local path (True: one big GEMM, False:
    blocked stacks); ``None`` lets the planner decide under ``auto``
    and means True for a fixed algorithm (the legacy default).  For the
    blocked path ``stack_size``/``align`` default to the smm autotune
    winners table for the block geometry and occupancy bin.  ``a_mask``
    / ``b_mask`` are *global* block occupancy masks ((M/block_m,
    K/block_k) / (K/block_k, N/block_n) numpy bool); the blocked path
    then plans only present triples per data-exchange step and skips
    steps whose mask product is empty (see module docstring).  The
    densified path ignores them (absent blocks are zeros, the single
    big GEMM is already correct).

    Norm-based on-the-fly filtering (repro.sparsity): with
    ``filter_eps`` not None, product contributions whose block-norm
    bound ``norm(A_ik) * norm(B_kj)`` falls below eps are dropped
    before they reach a multiplication stack.  ``a_norms`` /
    ``b_norms`` are *global* per-block Frobenius norms (block-grid
    float arrays); when omitted they are computed on the fly from the
    payloads (requires concrete arrays — call outside jit, as with
    ``return_plan``).  Norms are sliced by the same step map as the
    masks (core/steps.py; the union view's SPMD union becomes
    union-of-max), a step with no retained triple is skipped
    entirely, and the planner's occupancy becomes the norm-predicted
    retained fraction.  ``filter_eps=0.0`` is bit-identical to the
    unfiltered path; the densified local path ignores triple filtering
    (one big GEMM computes everything — filtering there is only the
    caller's post-multiply mask, see dbcsr.multiply).  ``stack_bins``
    caps the stack executor's size-bin count (core/engine.py;
    DBCSR_STACK_BINS env overrides the default 4).

    Rank-exact execution (module docstring): ``rank_exact=None`` (the
    default) runs every masked/filtered blocked multi-rank step from a
    stacked per-rank plan slab — each rank executes exactly its own
    retained triples, selected by ``axis_index`` inside shard_map —
    while ``False`` restores the legacy union-of-ranks plan and
    ``True`` forces per-rank slabs even when auto would collapse.
    Dense and uniform-fill steps collapse to the union executor
    bitwise; with ``filter_eps > 0`` the per-rank norm filter is
    EXACT per rank (the union applies the max norm product over
    ranks, so it under-filters).  ``rebalance`` controls the costed
    block-row/col permutation pass (repro.sparsity.balance): ``None``
    defers to the planner (applied only when the predicted compute
    saved by flattening per-rank load imbalance exceeds the shuffle's
    amortized cost — ``plan.rebalance``), ``True`` forces it,
    ``False`` disables it.  The permutation touches only block rows of
    A/C and block cols of B/C (never K: that would reorder every C
    block's accumulation), and is inverted on C before returning.

    Dispatch: the densified path runs as one cached ``jax.jit``
    program per key (algorithm, mesh, grid, pipeline depth, precision,
    local kernel, the schedule's keyword options and the operands'
    shapes, dtypes and shardings), so a warm call with fresh values
    neither traces, lowers nor compiles; under an outer ``jax.jit`` the
    program is traced into the caller's.  The blocked path's local
    multiply closes over this call's host plans (masks, norms,
    rank-exact steps, rebalance permutations), so it still builds and
    runs an eager ``shard_map`` closure on every call.

    ``pipeline_depth`` (core/schedule.py): 2 = double-buffered
    comm/compute overlap, 1 = serial (bit-identical output), 0 = rolled
    fori_loop ablation; ``None`` takes the plan's depth under ``auto``
    and the overlap default otherwise.  ``double_buffer`` is the legacy
    spelling (True -> 2, False -> 0).

    ``verify`` — ABFT self-verification (repro.robustness.abft):
    ``"checksum"`` verifies the product against independently computed
    Huang–Abraham block checksums (norm-aware tolerances so eps
    filtering and float accumulation never false-positive), localizes
    any corrupted block, and repairs it by one deterministic recompute
    of the flagged blocks; ``"auto"`` enables verification only when
    its priced overhead fits ``verify_budget`` (default 25%) of the
    plan's predicted time; ``None`` (default) is bit-identical to the
    pre-verification dispatcher with zero added work.  The outcome
    lands on the returned plan as ``plan.verification`` (pricing +
    :class:`~repro.robustness.abft.VerificationReport`).  Unrepairable
    corruption raises
    :class:`~repro.robustness.guards.CorruptionDetectedError`.

    ``return_plan=True`` returns ``(C, MultiplyPlan)`` where the plan
    records the planner's decision (with per-candidate predicted costs,
    see ``MultiplyPlan.explain()``) plus the executed blocked-path
    stack statistics (``executor_stats``) and the per-step comm/compute
    split of the executed schedule (``schedule_stats``).  Only usable
    outside jit — the plan is a host-side object.

    Spans (repro.obs): outside ``jax.jit`` tracing the call opens the
    profiler annotations ``dbcsr.multiply`` ⊃ ``dbcsr.plan``,
    ``dbcsr.stacks``, ``dbcsr.dispatch``, ``dbcsr.finish`` (plus
    ``dbcsr.verify`` ⊃ ``dbcsr.repair``), with the JAX runtime's
    lowerings and compiles on the root and the dispatch, a densified
    dispatch's ``program_cache`` ("hit" or "miss"), and the dispatch's
    ``comm_steps`` and ``comm_bytes`` (``_comm_count``: the collective
    phases and the most bytes any chip receives, held beside a
    densified program, counted per call on the blocked path only when
    a profiler session or telemetry takes it), and its ``skew_panels``
    (``schedule.skew_panels``: 2 where the schedule runs its skew and
    steps on row/column panels, else 1; held beside a densified
    program); inactive with no profiler session.  With
    ``obs.enable()`` active — and only then — they are also recorded as
    spans, the dispatch waits for the device, the counters
    ``dispatch.program_cache.hits`` / ``.misses`` count densified
    dispatches and ``schedule.comm_bytes`` sums ``comm_bytes``, and the
    plan's predicted-vs-measured cost is logged for the planner
    scoreboard.  The output is bit identical either way.
    """
    call = dict(
        mesh=mesh, grid=grid, algorithm=algorithm, densify=densify,
        block_m=block_m, block_k=block_k, block_n=block_n,
        stack_size=stack_size, align=align, local_kernel=local_kernel,
        a_mask=a_mask, b_mask=b_mask, a_norms=a_norms, b_norms=b_norms,
        filter_eps=filter_eps, stack_bins=stack_bins,
        rank_exact=rank_exact, rebalance=rebalance, precision=precision,
        pipeline_depth=pipeline_depth, double_buffer=double_buffer,
        verify=verify, verify_budget=verify_budget,
        return_plan=return_plan, **kw)
    live = is_live(a, b)
    with obs.maybe_span(live, "multiply", cat="multiply", counters=True,
                        **root_attrs(algorithm, a, b)):
        return _distributed_matmul(a, b, _live=live, **call)


def is_live(*operands) -> bool:
    """Spans open only for concrete operands, never while ``jax.jit``
    traces the call."""
    return not any(isinstance(x, jax.core.Tracer) for x in operands)


def root_attrs(algorithm: str, a, b) -> dict:
    """The root span's attrs: the requested algorithm and the shape."""
    attrs = {"algorithm": algorithm}
    if getattr(a, "ndim", 0) == 2 and getattr(b, "ndim", 0) == 2:
        attrs.update(m=int(a.shape[0]), k=int(a.shape[1]),
                     n=int(b.shape[1]))
    return attrs


def _distributed_matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    mesh: jax.sharding.Mesh,
    grid: GridSpec = GridSpec(),
    algorithm: str = "auto",
    densify: Optional[bool] = None,
    block_m: int = 64,
    block_k: int = 64,
    block_n: int = 64,
    stack_size: Optional[int] = None,
    align: Optional[bool] = None,
    local_kernel: Optional[str] = None,
    a_mask: Optional[np.ndarray] = None,
    b_mask: Optional[np.ndarray] = None,
    a_norms: Optional[np.ndarray] = None,
    b_norms: Optional[np.ndarray] = None,
    filter_eps: Optional[float] = None,
    stack_bins: Optional[int] = None,
    rank_exact: Optional[bool] = None,
    rebalance: Optional[bool] = None,
    precision=jax.lax.Precision.DEFAULT,
    pipeline_depth: Optional[int] = None,
    double_buffer: Optional[bool] = None,
    verify: Optional[str] = None,
    verify_budget: Optional[float] = None,
    return_plan: bool = False,
    _live: bool = False,
    _finish=None,
    **kw,
) -> jax.Array:
    """``distributed_matmul`` body (see its docstring), inside the
    caller's root span.  ``_live`` is the per-call span flag (False
    under jit tracing); spans are recorded, and the enabled path's
    synchronisation and plan-outcome log run, only when telemetry is
    on as well.  ``_finish(c, plan)``, when given, is the caller's own
    finishing of the product (``dbcsr.multiply``'s result mask and
    wrap), run inside the finish span; its value is returned.

    The dispatch (``_run``): densified, the cached program of
    ``_densified_program``, which records ``program_cache`` on the
    dispatch span; blocked, the algorithm's eager ``shard_map`` over
    this call's local multiply, then the inverse rebalance permutation.
    """
    _tele = _live and obs.enabled()
    m, k = a.shape
    k2, n = b.shape
    if k != k2:
        raise ValueError(f"inner dims disagree: {a.shape} @ {b.shape}")
    if verify not in (None, "checksum", "auto"):
        raise ValueError(
            f"verify must be None, 'checksum' or 'auto', got {verify!r}")

    with obs.maybe_span(_live, "plan", cat="plan") as psp:
        filtering = filter_eps is not None
        if filtering and a_norms is None and b_norms is None:
            # on-the-fly: derive the block norms from the payloads (one
            # blockwise reduction each; masked so absent blocks report 0)
            from repro.sparsity.norms import block_norms_of

            a_norms = block_norms_of(a, block_m, block_k, a_mask)
            b_norms = block_norms_of(b, block_k, block_n, b_mask)

        # ---- global mask/norm normalisation + rank-exact resolution -------
        # (hoisted above planning: the per-rank load imbalance of the
        # C-chunk decomposition feeds the planner's rank-exact pricing and
        # its costed rebalance decision)
        pr0, pc0 = grid.grid_shape(mesh)
        n_ranks_all = pr0 * pc0 * (1 if grid.stack_axis is None
                                   else grid.stack_size(mesh))
        masked = a_mask is not None or b_mask is not None or filtering
        am = bmk = an_g = bn_g = None
        if masked:
            am, bmk = _block_masks(m, k, n, block_m, block_k, block_n,
                                   a_mask, b_mask)
            if filtering:
                # norms ride the same slicing machinery as the masks;
                # mask-absent blocks are forced to norm 0 so one >= eps
                # comparison folds both criteria per rank
                from repro.sparsity.norms import normalize_block_norms

                an_g, bn_g = normalize_block_norms(
                    am.shape[0], am.shape[1], bmk.shape[1], a_norms, b_norms)
                an_g = np.where(am, an_g, np.float32(0.0))
                bn_g = np.where(bmk, bn_g, np.float32(0.0))
        use_rank = rank_exact is not False and masked and n_ranks_all > 1
        rank_imb = None
        if use_rank and am.shape[0] % pr0 == 0 and bmk.shape[1] % pc0 == 0:
            from repro.sparsity.balance import (chunk_imbalance,
                                                retained_block_weights)

            rank_imb = chunk_imbalance(
                retained_block_weights(am, bmk, an_g, bn_g, filter_eps),
                pr0, pc0)

        plan = None
        # telemetry forces a plan even for pinned algorithms: the planner
        # scoreboard needs predicted_s for every executed plan
        if algorithm == "auto" or return_plan or verify is not None or _tele:
            from repro.planner.plan import plan_multiply

            mesh_shape = ((pr0, pc0) if grid.stack_axis is None
                          else (pr0, pc0, grid.stack_size(mesh)))
            occ = _global_occupancy(m, k, n, block_m, block_k, block_n,
                                    a_mask, b_mask, a_norms, b_norms,
                                    filter_eps)
            # a pinned summa with the PUMMA broadcast prices through the
            # planner's "summa_gather" model — full-K gathered panels,
            # whose sqrt(P)-fold operand replication the mem feasibility
            # gate must see (auto never enumerates it; only this pin
            # reaches it)
            plan_algorithm = None if algorithm == "auto" else algorithm
            if algorithm == "summa" and kw.get("bcast") == "gather":
                plan_algorithm = "summa_gather"
            plan = plan_multiply(
                m, k, n, blocks=(block_m, block_k, block_n),
                mesh_shape=mesh_shape, occupancy=occ,
                dtype=jnp.promote_types(a.dtype, b.dtype),
                algorithm=plan_algorithm,
                # a fixed algorithm executes the legacy densified
                # default when densify is unset — the plan must describe
                # that, not the planner's own local-path preference
                densify=(densify
                         if algorithm == "auto" or densify is not None
                         else True),
                stack_size=stack_size, align=align,
                rank_imbalance=rank_imb)
            if algorithm == "auto":
                algorithm = plan.algorithm
                if densify is None:
                    densify = plan.densify
                if not densify:
                    if stack_size is None:
                        stack_size = plan.stack_tile
                    if align is None:
                        align = plan.align
                if pipeline_depth is None and double_buffer is None:
                    pipeline_depth = plan.pipeline_depth
            psp.set(algorithm=plan.algorithm, densify=bool(plan.densify),
                    predicted_s=float(plan.predicted_s),
                    occupancy=float(plan.occupancy),
                    trivial=bool(plan.trivial))
        if densify is None:
            densify = True  # legacy default for fixed algorithms
        if algorithm not in ("cannon", "cannon25d", "ts_k", "ts_m", "ts_n",
                            "summa"):
            raise ValueError(f"unknown algorithm {algorithm!r}")
        depth = resolve_pipeline_depth(pipeline_depth, double_buffer)

    with obs.maybe_span(_live, "stacks", cat="stacks"):
        # ---- costed rebalance: permute the block distribution -------------
        # The planner arms this only when predicted compute saved by
        # flattening per-rank load imbalance exceeds the shuffle's amortized
        # cost (plan.rebalance); ``rebalance=True/False`` overrides.  Only
        # block rows of A/C and block cols of B/C move — K stays identity so
        # every C block keeps its accumulation order — and the inverse
        # permutation is applied to C inside the re-runnable dispatch
        # closure (ABFT repair re-executions stay self-consistent).
        rb = None
        do_rebalance = (rebalance if rebalance is not None
                        else plan is not None and plan.rebalance)
        if (do_rebalance and not densify and use_rank
                and am.shape[0] % pr0 == 0 and bmk.shape[1] % pc0 == 0):
            from repro.sparsity.balance import plan_rebalance

            cand = plan_rebalance(am, bmk, pr0, pc0, a_norms=an_g,
                                  b_norms=bn_g, filter_eps=filter_eps)
            if not cand.identity:
                rb = cand
        a_exec, b_exec = a, b
        if rb is not None:
            from repro.sparsity.balance import (permute_block_cols,
                                                permute_block_rows)

            pm_idx, pn_idx = np.asarray(rb.perm_m), np.asarray(rb.perm_n)
            a_exec = permute_block_rows(a, rb.perm_m, block_m)
            b_exec = permute_block_cols(b, rb.perm_n, block_n)
            am = am[pm_idx]
            bmk = bmk[:, pn_idx]
            if an_g is not None:
                an_g = an_g[pm_idx]
            if bn_g is not None:
                bn_g = bn_g[:, pn_idx]
            obs.counter("planner.rebalance.applied").inc()

        # ---- local multiply geometry (per schedule step) ------------------
        pr, pc = grid.grid_shape(mesh)
        if algorithm.startswith("ts_"):
            p_all = pr * pc * grid.stack_size(mesh)
            shapes = {
                "ts_k": (m, k // p_all, n),
                "ts_m": (m // p_all, k, n),
                "ts_n": (m, k, n // p_all),
            }
            ml, kl, nl = shapes[algorithm]
        elif algorithm in ("cannon", "cannon25d"):
            # Local multiply is (m/pg, k/pg) @ (k/pg, n/pg) on the square
            # grid Cannon requires.  Deriving the inner dim from pc alone
            # (the old ``k // pc``) silently mis-sized B's stack-plan
            # geometry whenever pr != pc: gathers clamp out-of-range
            # block indices instead of failing, producing wrong C.
            pg = grid.validate_square(mesh)
            if (m % pg or k % pg or n % pg) and not densify:
                raise ValueError(
                    f"shape ({m},{k},{n}) not divisible by grid side {pg}")
            ml, kl, nl = m // pg, k // pg, n // pg
        else:
            # summa: every panel's local multiply is (m/pr, k/n_panels) @
            # (k/n_panels, n/pc) — one stack-plan geometry shared by all
            # panels, so non-square grids are fine (for square grids
            # k/n_panels == k/pc, the historical full-local-K geometry).
            # The all-gather broadcast is one panel: the local multiply
            # sees the gathered full-K row of A / column of B.
            n_panels = _summa_panels(pr, pc, kw)
            if (m % pr or n % pc or k % n_panels) and not densify:
                raise ValueError(
                    f"shape ({m},{k},{n}) not divisible by summa grid "
                    f"{pr}x{pc} with {n_panels} panels")
            ml, kl, nl = m // pr, k // n_panels, n // pc

        # ---- local multiply strategy (densified vs blocked) --------------
        if densify:
            lm = None   # the densified program builds its own
        else:
            blocked_kw = dict(
                block_m=block_m, block_k=block_k, block_n=block_n,
                stack_size=stack_size, align=align,
                kernel=local_kernel or "smm", stack_bins=stack_bins)
            if not masked:
                lm = blocked_local_matmul(ml, kl, nl, **blocked_kw)
            else:
                # which blocks each rank holds at each step, planned as
                # each rank's own slices or as their union
                smap = _step_map(algorithm, grid, mesh,
                                 (*am.shape, bmk.shape[1]), kw)
                steps = (rank_view(smap, am, bmk, an_g, bn_g) if use_rank
                         else [[u] for u in
                               union_view(smap, am, bmk, an_g, bn_g)])
                lm = _blocked_steps_lm(
                    ml, kl, nl, steps,
                    rank_index_fn=_rank_index_fn(algorithm, grid, mesh),
                    filter_eps=filter_eps, **blocked_kw)
        if not densify and obs.enabled():
            imb = _rank_imbalance_of(_rank_totals(lm))
            if imb is not None:
                obs.histogram("executor.rank_imbalance").observe(imb)

    # ---- data-exchange algorithm (all via the schedule engine) --------
    # The dispatch is wrapped in a re-runnable closure: at a fixed
    # config the whole pipeline is deterministic, so the ABFT repair
    # path re-executes it once and splices only the flagged blocks —
    # bitwise equal to a clean run (the densified path re-runs its
    # cached program).
    def _run():
        if densify:
            program, comm, hit = _densified_program(
                algorithm, a, b, mesh=mesh, grid=grid, depth=depth,
                precision=precision, local_kernel=local_kernel,
                local_shape=(ml, kl, nl), kw=kw)
            return program(a, b), hit, comm
        c = _schedule_matmul(
            algorithm, a_exec, b_exec, mesh=mesh, grid=grid,
            local_matmul=lm, precision=precision, pipeline_depth=depth,
            **kw)
        if rb is not None:
            from repro.sparsity.balance import (permute_block_cols,
                                                permute_block_rows)

            c = permute_block_rows(c, rb.inv_m, block_m)
            c = permute_block_cols(c, rb.inv_n, block_n)
        return c, None, None

    sched_stats_cache = [None]

    def _sched_stats():
        if sched_stats_cache[0] is None:
            itemsize = int(jnp.dtype(
                jnp.promote_types(a.dtype, b.dtype)).itemsize)
            sched_stats_cache[0] = _schedule_stats(
                algorithm, grid=grid, mesh=mesh, local_shape=(ml, kl, nl),
                itemsize=itemsize, lm=lm, densify=densify,
                pipeline_depth=depth, reduce_kw=kw)
        return sched_stats_cache[0]

    dispatch_times: List[float] = []

    def _run_traced():
        with obs.maybe_span(_live, "dispatch", cat="dispatch",
                            counters=True, algorithm=algorithm,
                            densify=bool(densify),
                            pipeline_depth=depth) as dsp:
            t0 = time.perf_counter()
            c, hit, comm = _run()
            if comm is None and dsp.recording():
                # the blocked path counts this call's schedule, and only
                # where a record or a profiler session takes the count
                comm = _counted(
                    algorithm, a_exec, b_exec, mesh=mesh, grid=grid,
                    local_shape=(ml, kl, nl),
                    empty_steps=getattr(lm, "empty_steps", frozenset()),
                    kw=kw)
            if comm:
                dsp.set(**comm)
            if hit is None:
                # a blocked multiply's stack plans span the whole blocks
                dsp.set(skew_panels=1)
            if hit is not None:
                dsp.set(program_cache="hit" if hit else "miss")
            if _tele:
                if hit is not None:
                    obs.counter("dispatch.program_cache."
                                + ("hits" if hit else "misses")).inc()
                if comm and "comm_bytes" in comm:
                    obs.counter("schedule.comm_bytes").inc(
                        comm["comm_bytes"])
            # telemetry off: enqueue and return — no timing, no sync
            if not _tele:
                return c
            c = jax.block_until_ready(c)
            dispatch_times.append(time.perf_counter() - t0)
        return c

    c = _run_traced()
    verification = None
    if verify is not None:
        c, verification = _verified_result(
            verify, a, b, c, _run_traced, plan=plan,
            block_m=block_m, block_k=block_k, block_n=block_n,
            a_mask=a_mask, b_mask=b_mask, a_norms=a_norms, b_norms=b_norms,
            filter_eps=filter_eps, verify_budget=verify_budget,
            _live=_live)
    with obs.maybe_span(_live, "finish", cat="finish"):
        if _tele and plan is not None and not plan.trivial \
                and dispatch_times:
            # predicted-vs-actual planner accounting: first dispatch is
            # the clean run (a repair re-execution would re-measure the
            # same deterministic program)
            obs.record_plan_outcome(
                kind="multiply", algorithm=algorithm, densify=bool(densify),
                m=m, k=k, n=n, occupancy=float(plan.occupancy),
                predicted_s=float(plan.predicted_s),
                measured_s=float(dispatch_times[0]),
                pipeline_depth=int(depth))
        if return_plan:
            es = _collect_executor_stats(lm, densify)
            if es is not None:
                es["rebalance_applied"] = rb is not None
                if rb is not None:
                    es["rebalance_method"] = rb.method
                    es["rebalance_imbalance_before"] = rb.imbalance_before
                    es["rebalance_imbalance_after"] = rb.imbalance_after
            plan = dataclasses.replace(
                plan,
                executor_stats=es,
                schedule_stats=_sched_stats(),
                verification=verification)
        out = (c, plan) if return_plan else c
        return out if _finish is None else _finish(*out)
