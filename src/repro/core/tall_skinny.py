"""Tall-and-skinny multiplication: O(1) per-process communication.

DBCSR's second data-exchange algorithm (paper section II, ref [13]):
when one matrix dimension is much larger than the others, Cannon's
O(1/sqrt(P)) volume is beaten by an algorithm whose per-process
communication is *independent of P*.

The paper's rectangular benchmark is M = N = 1'408, K = 1'982'464:
only the contraction dimension is large.  The TPU-native formulation:

  * shard K over *all* P devices (both mesh axes flattened),
  * replicate the small M and N dimensions,
  * local dot:  (M, K/P) @ (K/P, N) -> full (M, N) partial product,
  * one reduction over the flattened axis.

With ``reduce='all_reduce'`` every device receives the full (M, N)
result: communicated data per process ~ 2 * M * N bytes — O(1) in P,
matching the paper's claim.  ``reduce='reduce_scatter'`` leaves C
row-sharded and moves (P-1)/P * M*N per device, strictly less.

Two degenerate variants are provided for the other tall-skinny shapes:
  * M large (A tall): shard M, replicate B — **zero** communication.
  * N large (B wide): shard N, replicate A — zero communication.

Each variant is a one-step schedule (``build_ts_schedule``);
``ts_step_map`` says which blocks each device multiplies in that step,
and the blocked path plans its stacks from it (core/steps.py).
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .blocking import GridSpec
from .cannon import _default_local_matmul
from .schedule import Schedule, execute_schedule, resolve_pipeline_depth
from .steps import StepMap

__all__ = ["tall_skinny_matmul", "build_ts_schedule", "ts_specs",
           "ts_step_map", "classify_shape", "ts_classify_ratio",
           "DEFAULT_TS_RATIO"]

# The historical hardcoded tall/skinny threshold.  The live threshold
# is planner-owned (the cost-model crossover where tall-skinny's O(1)
# communication beats Cannon's O(1/sqrt(P)) — see
# repro.planner.cost_model.ts_crossover_ratio); this constant is the
# fallback when the planner cannot produce one.
DEFAULT_TS_RATIO = 8.0

_RATIO_CACHE: float | None = None


def ts_classify_ratio(refresh: bool = False) -> float:
    """The dominance ratio at which ``classify_shape`` switches from
    Cannon to a tall-skinny variant.

    Exported so callers can inspect *why* a shape was classified
    tall/skinny: a shape is ``ts_<dim>`` iff its largest dimension is at
    least ``ts_classify_ratio()`` times each other dimension.  Computed
    once per process from the planner's cost-model crossover (hardware
    constants from repro.planner.calibrate), falling back to the legacy
    ``DEFAULT_TS_RATIO`` when the planner is unavailable.
    """
    global _RATIO_CACHE
    if _RATIO_CACHE is None or refresh:
        try:
            from repro.planner.cost_model import ts_crossover_ratio

            _RATIO_CACHE = float(ts_crossover_ratio())
        except Exception:
            _RATIO_CACHE = DEFAULT_TS_RATIO
    return _RATIO_CACHE


def classify_shape(m: int, k: int, n: int,
                   ratio: float | None = None) -> str:
    """Pick the data-exchange algorithm from the global shape.

    Mirrors DBCSR's dispatch: 'cannon' for general matrices,
    'ts_k' / 'ts_m' / 'ts_n' when one dimension dominates by at least
    ``ratio`` (default: the planner-owned ``ts_classify_ratio()``).

    Note: ``distributed_matmul(algorithm="auto")`` no longer dispatches
    through this shape heuristic alone — it evaluates the full
    cost-model candidate space (repro.planner.plan_multiply), which
    also accounts for occupancy, local path, and mesh geometry.  This
    classifier remains the shape-only view of that decision.
    """
    if ratio is None:
        ratio = ts_classify_ratio()
    dims = {"m": m, "k": k, "n": n}
    big = max(dims, key=dims.get)
    others = [v for kk, v in dims.items() if kk != big]
    if dims[big] >= ratio * max(others):
        return f"ts_{big}"
    return "cannon"


def build_ts_schedule(
    mode: str,
    axes,
    *,
    reduce: str = "reduce_scatter",
    local_shape: Optional[tuple] = None,
    itemsize: int = 4,
) -> Schedule:
    """Schedule for the tall-and-skinny variants: a single compute step
    (operands arrive pre-sharded over ``axes``), with the O(1)-in-P
    reduction of the (m, n) partial product as the epilogue (ts_k) or
    no communication at all (ts_m / ts_n)."""
    if mode not in ("ts_k", "ts_m", "ts_n"):
        raise ValueError(mode)
    epilogue_bytes = 0

    if mode == "ts_k":
        if reduce == "all_reduce":
            def epilogue(c):
                return jax.lax.psum(c, axes)   # O(1): ~2*M*N per device
        elif reduce == "reduce_scatter":
            def epilogue(c):
                return jax.lax.psum_scatter(
                    c, axes, scatter_dimension=0, tiled=True
                )                              # (P-1)/P * M*N per device
        else:
            raise ValueError(reduce)
        comm_op = f"psum{'_scatter' if reduce == 'reduce_scatter' else ''}"
        if local_shape is not None:
            ml, _, nl = local_shape
            epilogue_bytes = 2 * ml * nl * 4   # f32 partial both ways
    else:
        epilogue = None
        comm_op = "none (operand pre-replicated)"

    kw = {} if epilogue is None else {"epilogue": epilogue}
    return Schedule(
        algorithm=mode,
        n_steps=1,
        comm_op=comm_op,
        epilogue_comm_bytes=epilogue_bytes,
        **kw,
    )


@functools.lru_cache(maxsize=256)
def ts_step_map(mode: str, nbr: int, nbk: int, nbc: int,
                p_all: int) -> StepMap:
    """The step map (core/steps.py) of a tall-and-skinny variant over an
    (nbr, nbk, nbc) block grid: one step, at which device ``d`` of the
    ``p_all`` shards (the joint mesh axes flattened) multiplies its A
    column chunk by its B row chunk (``ts_k``), its A row chunk by all
    of B (``ts_m``) or all of A by its B column chunk (``ts_n``)."""
    dims = {"ts_k": ("K", nbk), "ts_m": ("M", nbr), "ts_n": ("N", nbc)}
    if mode not in dims:
        raise ValueError(mode)
    name, nb = dims[mode]
    if nb % p_all:
        raise ValueError(f"{name} block grid {nb} not divisible by {p_all}")
    chunk = nb // p_all
    if mode == "ts_k":
        return StepMap.of([[(0, d * chunk, 0) for d in range(p_all)]],
                          (nbr, chunk, nbc), pair=True)
    if mode == "ts_m":
        return StepMap.of([[(d * chunk, 0, 0) for d in range(p_all)]],
                          (chunk, nbk, nbc), pair=False)
    return StepMap.of([[(0, 0, d * chunk) for d in range(p_all)]],
                      (nbr, nbk, chunk), pair=False)


def tall_skinny_matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    mesh: jax.sharding.Mesh,
    grid: GridSpec = GridSpec(),
    mode: str = "ts_k",
    reduce: str = "reduce_scatter",
    local_matmul: Optional[Callable] = None,
    out_dtype=None,
    precision=jax.lax.Precision.DEFAULT,
    pipeline_depth: Optional[int] = None,
) -> jax.Array:
    """C = A @ B with the tall-and-skinny algorithm.

    mode='ts_k': A (M,K) sharded P(None, (row,col)), B (K,N) sharded
      P((row,col), None); C replicated or row-sharded.
    mode='ts_m': A sharded P((row,col), None), B replicated; C row-sharded.
    mode='ts_n': A replicated, B sharded P(None, (row,col)); C col-sharded.

    The single compute step routes through the schedule engine for
    uniformity; ``pipeline_depth`` is accepted but has no overlap to
    express on a one-step schedule.
    """
    axes = (grid.row_axis, grid.col_axis) if grid.stack_axis is None else (
        grid.stack_axis, grid.row_axis, grid.col_axis)
    if out_dtype is None:
        out_dtype = jnp.promote_types(a.dtype, b.dtype)
    lm = local_matmul or _default_local_matmul(precision)
    depth = resolve_pipeline_depth(pipeline_depth)
    sched = build_ts_schedule(mode, axes, reduce=reduce)
    # ts_k reduces f32 partials (legacy semantics); the zero-comm
    # ts_m/ts_n variants historically cast the single local dot straight
    # to out_dtype — accumulate there, not in f32, so f64/int operands
    # keep full precision
    accum = jnp.float32 if mode == "ts_k" else out_dtype

    def body(a_blk, b_blk):
        return execute_schedule(sched, a_blk, b_blk, local_matmul=lm,
                                out_dtype=out_dtype, pipeline_depth=depth,
                                accum_dtype=accum)

    in_specs, out_spec = ts_specs(mode, axes, reduce)
    fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=out_spec, check_vma=False)
    return fn(a, b)


def ts_specs(mode: str, axes, reduce: str = "reduce_scatter"):
    """``((A spec, B spec), C spec)`` of a tall-and-skinny variant's
    ``shard_map``: operands arriving in another layout are resharded to
    these at its boundary."""
    if mode == "ts_m":
        # zero-communication: shard the tall output dimension
        return (P(axes, None), P(None, None)), P(axes, None)
    if mode == "ts_n":
        return (P(None, None), P(None, axes)), P(None, axes)
    return ((P(None, axes), P(axes, None)),
            P(None, None) if reduce == "all_reduce" else P(axes, None))
