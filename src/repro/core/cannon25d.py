"""2.5D Cannon over the pod axis (beyond-paper, from the DBCSR lineage).

Lazzaro et al. [paper ref 10] extended DBCSR with a 2.5D algorithm:
keep c replicas of A and B on c stacked process grids, let replica p
execute only 1/c of the k-shift steps (offset by p * P/c), and combine
the partial C's with one reduction over the stack axis.  Per-replica
communication drops from O(sqrt(P)) shifts to O(sqrt(P)/c) at the cost
of c-fold operand replication — the classic communication-avoiding
trade.

On the production mesh the replication axis is the **pod** axis
(2 pods => c = 2): inter-pod ICI/DCN carries only the final C
reduction, while all Cannon shifts stay on the intra-pod torus.  This
is exactly the property you want at 1000+ node scale: the slow
cross-pod links see O(M*N/P) bytes once, never the O(sqrt(P)) shift
traffic.

SPMD note: the per-replica step offset must NOT be implemented with
control flow on the replica index — collectives inside divergent
branches deadlock (all devices must issue the same collective
sequence).  Instead the offset is folded into the initial skew as one
*static* joint-axis ppermute over (stack, row, col): device (p, i, j)
starts from A(i, (i + j + p*P/c) % P) and B((i + j + p*P/c) % P, j).

The step loop itself is the unified schedule engine (core/schedule.py):
``build_cannon25d_schedule`` composes the Cannon shift schedule with
the fused-skew prologue and the stack-axis reduction epilogue.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .blocking import GridSpec
from .cannon import _default_local_matmul, build_cannon_schedule
from .schedule import Schedule, execute_schedule, resolve_pipeline_depth

__all__ = ["cannon25d_matmul", "build_cannon25d_schedule"]


def _skew25d_perm(pg: int, c_repl: int, spr: int, which: str):
    """Static permutation over flattened (stack, row, col):
    destination (p, i, j) receives
      A block (i, (i + j + p*spr) % P)  — held by source (p, i, (i+j+p*spr)%P)
      B block ((i + j + p*spr) % P, j)  — held by source (p, (i+j+p*spr)%P, j)
    (sources stay within their own pod: A/B enter replicated over pods).
    """
    flat = lambda p, i, j: (p * pg + i) * pg + j
    pairs = []
    for p in range(c_repl):
        for i in range(pg):
            for j in range(pg):
                k = (i + j + p * spr) % pg
                if which == "a":
                    pairs.append((flat(p, i, k), flat(p, i, j)))
                else:
                    pairs.append((flat(p, k, j), flat(p, i, j)))
    return pairs


def build_cannon25d_schedule(
    pg: int,
    c_repl: int,
    *,
    row_axis: str,
    col_axis: str,
    stack_axis: str,
    reduce: str = "all_reduce",
    empty_steps: frozenset = frozenset(),
    local_shape: Optional[tuple] = None,
    itemsize: int = 4,
) -> Schedule:
    """Schedule for 2.5D Cannon: the Cannon shift steps (1/c of them,
    replica-offset via the fused-skew prologue) plus one partial-C
    reduction over the stack axis as the epilogue."""
    if pg % c_repl:
        raise ValueError(f"grid side {pg} not divisible by replication {c_repl}")
    spr = pg // c_repl  # steps per replica
    base = build_cannon_schedule(
        pg, row_axis=row_axis, col_axis=col_axis, skew=False, steps=spr,
        empty_steps=empty_steps, local_shape=local_shape, itemsize=itemsize)
    axes3 = (stack_axis, row_axis, col_axis)

    def prologue(a_blk, b_blk):
        # fused skew + replica offset: one static joint-axis ppermute
        a_blk = jax.lax.ppermute(a_blk, axes3,
                                 _skew25d_perm(pg, c_repl, spr, "a"))
        b_blk = jax.lax.ppermute(b_blk, axes3,
                                 _skew25d_perm(pg, c_repl, spr, "b"))
        return (a_blk, b_blk)

    def epilogue(c_partial):
        if reduce == "all_reduce":
            return jax.lax.psum(c_partial, stack_axis)
        if reduce == "reduce_scatter":
            return jax.lax.psum_scatter(
                c_partial, stack_axis, scatter_dimension=0, tiled=True)
        raise ValueError(reduce)

    prologue_bytes = epilogue_bytes = 0
    if local_shape is not None:
        ml, kl, nl = local_shape
        prologue_bytes = (ml * kl + kl * nl) * itemsize
        # partial C's reduce in f32 over the stack axis
        epilogue_bytes = 2 * ml * nl * 4

    return base.replace(
        algorithm="cannon25d",
        prologue=prologue,
        epilogue=epilogue,
        prologue_comm_bytes=prologue_bytes,
        epilogue_comm_bytes=epilogue_bytes,
        # the fused skew is a whole-block ppermute, as are the shifts
        row_col_panels=pg > 1,
    )


def cannon25d_matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    mesh: jax.sharding.Mesh,
    grid: GridSpec,
    local_matmul: Optional[Callable] = None,
    out_dtype=None,
    precision=jax.lax.Precision.DEFAULT,
    pipeline_depth: Optional[int] = None,
    double_buffer: Optional[bool] = None,
    reduce: str = "all_reduce",  # or "reduce_scatter"
) -> jax.Array:
    """C = A @ B, 2.5D Cannon with replication over ``grid.stack_axis``.

    A, B enter 2D-sharded over (row, col) and replicated over the stack
    axis — spec P(row, col).  C leaves with the same spec (all_reduce)
    or additionally row-sharded over the stack axis (reduce_scatter).
    ``pipeline_depth`` follows core/schedule.py semantics.
    """
    if grid.stack_axis is None:
        raise ValueError("cannon25d needs grid.stack_axis (e.g. 'pod')")
    pg = grid.validate_square(mesh)
    c_repl = grid.stack_size(mesh)
    if out_dtype is None:
        out_dtype = jnp.promote_types(a.dtype, b.dtype)
    lm = local_matmul or _default_local_matmul(precision)
    depth = resolve_pipeline_depth(pipeline_depth, double_buffer)
    sched = build_cannon25d_schedule(
        pg, c_repl, row_axis=grid.row_axis, col_axis=grid.col_axis,
        stack_axis=grid.stack_axis, reduce=reduce,
        empty_steps=getattr(lm, "empty_steps", frozenset()))

    def body(a_blk, b_blk):
        return execute_schedule(sched, a_blk, b_blk, local_matmul=lm,
                                out_dtype=out_dtype, pipeline_depth=depth)

    spec2d = P(grid.row_axis, grid.col_axis)
    if reduce == "all_reduce":
        out_spec = spec2d
    else:
        # psum_scatter chunk p of the local block goes to pod p => the
        # stack axis is the *minor* factor of the row partition.
        out_spec = P((grid.row_axis, grid.stack_axis), grid.col_axis)
    fn = jax.shard_map(body, mesh=mesh, in_specs=(spec2d, spec2d),
                       out_specs=out_spec, check_vma=False)
    return fn(a, b)
