"""Product-batched distributed multiply: N block-sparse products, ONE
fused dispatch.

Many workloads (density-matrix purification over k-point batches,
ensemble propagation, batched NEGF) issue MANY independent block-sparse
products of the same block geometry.  Dispatching them through
``distributed_matmul`` one by one pays the per-product dispatch price N
times over: each call traces its own shard_map program, builds its own
stack plans, and launches its own scan — and on small products the
host-side dispatch dominates the device time (the batched-GPU
observation of Mijić & Davidović, arXiv:2203.09353).

``distributed_matmul_batched`` stacks the G operand pairs as
``(G, m, k) @ (G, k, n)`` and runs ONE schedule over them:

  * the data-exchange schedule (Cannon shifts / SUMMA panel broadcasts)
    is shape-agnostic over leading batch dims, so the G products ride
    one ppermute/psum sequence — G times the payload per message, same
    message count (latency amortization);
  * the blocked local path fuses the per-group stack plans into one
    group-offset stack tensor (core/engine.py
    ``BatchedExecutorPlan``) executed by a single ``lax.scan`` through
    ``grouped_process_stack`` — one trace for the whole batch;
  * the densified local path becomes one grouped GEMM
    ``(G, ml, kl) @ (G, kl, nl)`` (kernels/grouped_gemm).

Supported data-exchange algorithms: ``cannon`` and ``summa`` (psum
broadcast) — the two whose schedules are batch-shape-agnostic.  The
tall-skinny and 2.5D variants reshape over mesh axes in ways that are
not worth generalizing for the batched service (their target regimes —
one huge skinny product, one huge square product — are not
many-small-products regimes).

Per-product occupancy masks and norms are accepted as *sequences*
(``a_masks[g]`` etc.); the fused plan covers every group's present
triples and a data-exchange step is skipped only when it is empty for
EVERY group.

Bit-identity contract: at ``pipeline_depth=1`` (serial) with
``filter_eps`` in {None, 0.0}, the blocked path of the fused batch is
bit-identical to G sequential ``distributed_matmul`` calls — stack
fusion never reorders any C block's k-run and padding rows only touch
the global scratch block (see ``execute_batched_plan``).  The densified
path is numerically equivalent but not bitwise-guaranteed (the grouped
Pallas GEMM may tile differently from per-product ``lax.dot``).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

from .blocking import GridSpec
from .cannon import cannon_matmul
from .densify import grouped_densified_local_matmul
from .engine import batched_stack_executor
from .multiply import (_block_masks, _global_occupancy, _schedule_stats,
                       _step_map, _stepwise_lm, is_live)
from .schedule import resolve_pipeline_depth
from .steps import union_view
from .summa import summa_matmul, summa_n_panels
# canonical definition lives with the cost model (the planner restricts
# its batched enumeration by it; cost_model imports nothing from core)
from repro.planner.cost_model import BATCHED_ALGORITHMS

__all__ = ["distributed_matmul_batched", "BATCHED_ALGORITHMS"]


def _per_group(seq: Optional[Sequence], g: int, n_groups: int, name: str):
    """Normalise an optional per-group sequence argument."""
    if seq is None:
        return None
    if len(seq) != n_groups:
        raise ValueError(f"{name} has {len(seq)} entries for {n_groups} "
                         f"products")
    return seq[g]


def _collect_batched_executor_stats(lm, densify: bool) -> Optional[dict]:
    """Aggregate the executed fused dispatch's padding / cross-request
    fusion statistics (attached to executed plans as
    ``executor_stats``)."""
    if densify:
        return None
    if getattr(lm, "stepwise", False):
        plans = [f.batched_plan for f in lm.step_executors if f is not None]
        n_steps = len(lm.step_executors)
    else:
        plan = getattr(lm, "batched_plan", None)
        plans = [] if plan is None else [plan]
        n_steps = 1
    if not plans:
        return None
    n_entries = sum(p.n_entries for p in plans)
    n_padding = sum(p.n_padding for p in plans)
    total = sum(p.n_stacks * p.stack_tile for p in plans)
    return {
        "n_groups": plans[0].n_groups,
        "n_steps": n_steps,
        "n_empty_steps": len(getattr(lm, "empty_steps", frozenset())),
        "n_fused_dispatches": len(plans),
        # groups whose per-step plan hit another group's memo entry —
        # the cross-request plan-sharing win of bucketing by content
        "n_shared_plans": sum(p.n_shared_plans for p in plans),
        "n_entries": n_entries,
        "n_padding": n_padding,
        "padding_frac": n_padding / total if total else 0.0,
        "per_step": [p.stats() for p in plans],
    }


def distributed_matmul_batched(
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
    a_masks: Optional[Sequence[Optional[np.ndarray]]] = None,
    b_masks: Optional[Sequence[Optional[np.ndarray]]] = None,
    a_norms: Optional[Sequence[Optional[np.ndarray]]] = None,
    b_norms: Optional[Sequence[Optional[np.ndarray]]] = None,
    filter_eps: Optional[float] = None,
    precision=jax.lax.Precision.DEFAULT,
    pipeline_depth: Optional[int] = None,
    double_buffer: Optional[bool] = None,
    return_plan: bool = False,
    **kw,
):
    """C[g] = A[g] @ B[g] for every product ``g`` of a fused batch.

    Outside ``jax.jit`` tracing the call opens the profiler annotations
    ``dbcsr.multiply_batched`` ⊃ ``dbcsr.plan``, ``dbcsr.stacks``,
    ``dbcsr.dispatch``, ``dbcsr.finish``, with the JAX runtime's
    lowerings and compiles on the root and the dispatch; with telemetry
    on (``obs.enable()``) they are also recorded, the dispatch waits
    for the device, and the batched plan's predicted-vs-measured fused
    cost is logged.  The output is bit identical either way.  See
    ``_distributed_matmul_batched`` for semantics.
    """
    call = dict(
        mesh=mesh, grid=grid, algorithm=algorithm, densify=densify,
        block_m=block_m, block_k=block_k, block_n=block_n,
        stack_size=stack_size, align=align, local_kernel=local_kernel,
        a_masks=a_masks, b_masks=b_masks, a_norms=a_norms, b_norms=b_norms,
        filter_eps=filter_eps, precision=precision,
        pipeline_depth=pipeline_depth, double_buffer=double_buffer,
        return_plan=return_plan, **kw)
    attrs = {"algorithm": algorithm}
    if getattr(a, "ndim", 0) == 3 and getattr(b, "ndim", 0) == 3:
        attrs.update(n_groups=int(a.shape[0]), m=int(a.shape[1]),
                     k=int(a.shape[2]), n=int(b.shape[2]))
    live = is_live(a, b)
    with obs.maybe_span(live, "multiply_batched", cat="multiply",
                        counters=True, **attrs):
        return _distributed_matmul_batched(a, b, _live=live, **call)


def _distributed_matmul_batched(
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
    a_masks: Optional[Sequence[Optional[np.ndarray]]] = None,
    b_masks: Optional[Sequence[Optional[np.ndarray]]] = None,
    a_norms: Optional[Sequence[Optional[np.ndarray]]] = None,
    b_norms: Optional[Sequence[Optional[np.ndarray]]] = None,
    filter_eps: Optional[float] = None,
    precision=jax.lax.Precision.DEFAULT,
    pipeline_depth: Optional[int] = None,
    double_buffer: Optional[bool] = None,
    return_plan: bool = False,
    _live: bool = False,
    **kw,
):
    """C[g] = A[g] @ B[g] for every product ``g`` of a fused batch.

    ``a``: (G, M, K) and ``b``: (G, K, N), both sharded over the
    trailing two axes exactly like the single-product
    ``distributed_matmul`` operands (the leading product dim is
    replicated).  ``algorithm`` is ``"auto"`` (planner-resolved,
    restricted to the batch-capable set), ``"cannon"`` or ``"summa"``
    (psum broadcast; ``bcast="gather"`` is not supported batched).

    Per-product sparsity: ``a_masks`` / ``b_masks`` / ``a_norms`` /
    ``b_norms`` are length-G sequences (entries may be None = dense);
    ``filter_eps`` is shared by the whole batch — the batching service
    buckets requests by eps, so a fused batch is eps-uniform by
    construction.  When filtering without explicit norms they are
    derived per product from the payloads (outside jit only).

    ``return_plan=True`` returns ``(C, BatchedMultiplyPlan)`` with the
    planner's fuse-vs-loop pricing and the executed fused dispatch's
    padding / plan-sharing statistics (``executor_stats``).

    See the module docstring for the bit-identity contract vs G looped
    ``distributed_matmul`` calls.
    """
    if a.ndim != 3 or b.ndim != 3:
        raise ValueError(f"batched operands must be (G, M, K) x (G, K, N), "
                         f"got {a.shape} x {b.shape}")
    g_count, m, k = a.shape
    gb, k2, n = b.shape
    if gb != g_count or k != k2:
        raise ValueError(f"batched operands disagree: {a.shape} @ {b.shape}")
    if g_count < 1:
        raise ValueError("batched multiply needs at least one product")
    if kw.get("bcast") == "gather":
        raise ValueError("bcast='gather' is not supported for batched "
                         "dispatch (the all-gathered full-K row would be "
                         "replicated per product)")

    _tele = _live and obs.enabled()
    with obs.maybe_span(_live, "plan", cat="plan") as psp:
        filtering = filter_eps is not None
        if filtering and a_norms is None and b_norms is None:
            from repro.sparsity.norms import block_norms_of

            a_norms = [block_norms_of(
                a[gi], block_m, block_k,
                _per_group(a_masks, gi, g_count, "a_masks"))
                for gi in range(g_count)]
            b_norms = [block_norms_of(
                b[gi], block_k, block_n,
                _per_group(b_masks, gi, g_count, "b_masks"))
                for gi in range(g_count)]

        plan = None
        # telemetry forces a plan even for pinned algorithms (scoreboard
        # needs the predicted fused cost)
        if algorithm == "auto" or return_plan or _tele:
            from repro.planner.plan import plan_multiply_batched

            pr0, pc0 = grid.grid_shape(mesh)
            occs = [
                _global_occupancy(
                    m, k, n, block_m, block_k, block_n,
                    _per_group(a_masks, gi, g_count, "a_masks"),
                    _per_group(b_masks, gi, g_count, "b_masks"),
                    _per_group(a_norms, gi, g_count, "a_norms"),
                    _per_group(b_norms, gi, g_count, "b_norms"),
                    filter_eps)
                for gi in range(g_count)
            ]
            occ = sum(occs) / len(occs)
            occ_max = max(occs)
            # groups pad to the largest group's stack shape: the
            # mean/max occupancy spread estimates the fused dispatch's
            # padding waste
            pad_est = 1.0 - occ / occ_max if occ_max > 0 else 0.0
            plan = plan_multiply_batched(
                g_count, m, k, n, blocks=(block_m, block_k, block_n),
                mesh_shape=(pr0, pc0), occupancy=occ,
                dtype=jnp.promote_types(a.dtype, b.dtype),
                algorithm=None if algorithm == "auto" else algorithm,
                densify=(densify
                         if algorithm == "auto" or densify is not None
                         else True),
                padding_frac=pad_est, stack_size=stack_size, align=align)
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
            psp.set(algorithm=plan.algorithm, fuse=bool(plan.fuse),
                    densify=bool(plan.densify),
                    predicted_fused_s=float(plan.predicted_fused_s),
                    predicted_looped_s=float(plan.predicted_looped_s),
                    occupancy=float(occ), trivial=bool(plan.trivial))
        if densify is None:
            # mirror distributed_matmul's fixed-algorithm default
            densify = True
        if algorithm not in BATCHED_ALGORITHMS:
            raise ValueError(
                f"batched dispatch supports {BATCHED_ALGORITHMS}, got "
                f"{algorithm!r} (the tall-skinny / 2.5D schedules are not "
                f"batch-shape-agnostic)")
        depth = resolve_pipeline_depth(pipeline_depth, double_buffer)

    with obs.maybe_span(_live, "stacks", cat="stacks"):
        # ---- local multiply geometry ------------------------------------
        pr, pc = grid.grid_shape(mesh)
        if algorithm == "cannon":
            pg = grid.validate_square(mesh)
            if (m % pg or k % pg or n % pg) and not densify:
                raise ValueError(
                    f"shape ({m},{k},{n}) not divisible by grid side {pg}")
            ml, kl, nl = m // pg, k // pg, n // pg
        else:
            n_panels = summa_n_panels(pr, pc)
            if (m % pr or n % pc or k % n_panels) and not densify:
                raise ValueError(
                    f"shape ({m},{k},{n}) not divisible by summa grid "
                    f"{pr}x{pc} with {n_panels} panels")
            ml, kl, nl = m // pr, k // n_panels, n // pc

        # ---- local multiply strategy ------------------------------------
        no_masks = a_masks is None and b_masks is None
        if densify:
            lm = grouped_densified_local_matmul(precision, kernel=local_kernel)
        else:
            batched_kw = dict(
                block_m=block_m, block_k=block_k, block_n=block_n,
                stack_size=stack_size, align=align,
                kernel=local_kernel or "smm")
            if no_masks and not filtering:
                lm = batched_stack_executor(g_count, ml, kl, nl, **batched_kw)
            else:
                group_ab = []
                for gi in range(g_count):
                    am, bmk = _block_masks(
                        m, k, n, block_m, block_k, block_n,
                        _per_group(a_masks, gi, g_count, "a_masks"),
                        _per_group(b_masks, gi, g_count, "b_masks"))
                    an_g = bn_g = None
                    if filtering:
                        from repro.sparsity.norms import normalize_block_norms

                        an_g, bn_g = normalize_block_norms(
                            am.shape[0], am.shape[1], bmk.shape[1],
                            _per_group(a_norms, gi, g_count, "a_norms"),
                            _per_group(b_norms, gi, g_count, "b_norms"))
                        an_g = np.where(am, an_g, np.float32(0.0))
                        bn_g = np.where(bmk, bn_g, np.float32(0.0))
                    group_ab.append((am, bmk, an_g, bn_g))
                # one step map for the whole batch; a step is empty only
                # when every group's union is
                smap = _step_map(algorithm, grid, mesh,
                                 (m // block_m, k // block_k, n // block_n),
                                 kw)
                views = [union_view(smap, *ab) for ab in group_ab]
                lm = _stepwise_lm(
                    list(zip(*views)),
                    lambda gms: batched_stack_executor(
                        g_count, ml, kl, nl, group_masks=gms,
                        filter_eps=filter_eps, **batched_kw),
                    filter_eps)

    # ---- data exchange (one schedule for the whole batch) ------------
    def _run():
        if algorithm == "cannon":
            return cannon_matmul(
                a, b, mesh=mesh, grid=grid, local_matmul=lm,
                precision=precision, pipeline_depth=depth, **kw)
        return summa_matmul(
            a, b, mesh=mesh, grid=grid, local_matmul=lm,
            precision=precision, pipeline_depth=depth, **kw)

    dispatch_s = None
    with obs.maybe_span(_live, "dispatch", cat="dispatch", counters=True,
                        algorithm=algorithm, densify=bool(densify),
                        pipeline_depth=depth, n_groups=g_count) as dsp:
        if not _tele:
            c = _run()   # enqueue and return — no timing, no sync
        else:
            t0 = time.perf_counter()
            c = jax.block_until_ready(_run())
            dispatch_s = time.perf_counter() - t0
    with obs.maybe_span(_live, "finish", cat="finish"):
        if _tele:
            try:
                # G-scaled comm bytes of the single-product schedule
                itemsize = int(jnp.dtype(
                    jnp.promote_types(a.dtype, b.dtype)).itemsize)
                ss = _schedule_stats(
                    algorithm, grid=grid, mesh=mesh,
                    local_shape=(ml, kl, nl), itemsize=itemsize, lm=lm,
                    densify=densify, pipeline_depth=depth, reduce_kw=kw,
                    n_groups=g_count)
                dsp.set(comm_bytes=int(ss.get("total_comm_bytes", 0)))
            except Exception:
                pass  # telemetry must never break the multiply
            if plan is not None and not plan.trivial:
                obs.record_plan_outcome(
                    kind="multiply_batched", algorithm=algorithm,
                    densify=bool(densify), n_groups=g_count, m=m, k=k,
                    n=n, fuse=bool(plan.fuse),
                    predicted_s=float(plan.predicted_fused_s),
                    measured_s=float(dispatch_s), pipeline_depth=int(depth))
        if not return_plan:
            return c
        plan = dataclasses.replace(
            plan,
            executor_stats=_collect_batched_executor_stats(lm, densify))
        return c, plan
