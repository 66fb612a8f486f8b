"""Cannon's algorithm on a square mesh grid via shard_map + ppermute.

This is DBCSR's data-exchange algorithm for general matrix shapes
(paper section II): per-process communicated data scales O(1/sqrt(P)).

TPU adaptation notes (see DESIGN.md §2):
  * MPI async point-to-point sends -> ``jax.lax.ppermute`` neighbour
    shifts.  The TPU ICI is a torus, so Cannon's row/col shifts map to
    contention-free single-hop collective-permutes.
  * The initial Cannon skew (device (i,j) must start from A(i, (i+j)%P)
    and B((i+j)%P, j)) is one joint-axis ppermute over the flattened
    (row, col) axes.
  * Communication/computation overlap (paper: MPI/CUDA-stream double
    buffering) is owned by the schedule engine (core/schedule.py): at
    ``pipeline_depth=2`` the ppermute for step t+1 is issued against a
    second buffer *before* the local multiply of step t, and XLA
    schedules the collective-permute-start/done pair around the dot.

This module is a pure *schedule builder* plus the shard_map wrapper:
``build_cannon_schedule`` emits the step sequence (skew prologue,
identity recv, neighbour-shift carry update), ``cannon_step_map`` says
which A and B chunks each rank holds at each step (the blocked path
plans its stacks from it, core/steps.py), and the unified driver
(``schedule.execute_schedule``) runs the loop.

The local multiply is pluggable (``local_matmul``): ``densified`` uses a
single large dot (paper section III — the cuBLAS path), ``blocked``
dispatches the stack-of-small-blocks path (kernels/smm, LIBCUSMM
analogue).
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .blocking import GridSpec
from .schedule import (RolledSpec, Schedule, execute_schedule,
                       resolve_pipeline_depth)
from .steps import StepMap

__all__ = ["cannon_matmul", "build_cannon_schedule", "cannon_step_map"]


def _skew_perm(pg: int, which: str):
    """Joint-axis permutation realising the Cannon pre-skew.

    A: device (i, j) receives A block (i, (i+j) % P)  [row i shifted left i]
    B: device (i, j) receives B block ((i+j) % P, j)  [col j shifted up  j]
    Expressed as (source, destination) pairs over the row-major flattened
    (row, col) index space.
    """
    pairs = []
    for i in range(pg):
        for j in range(pg):
            if which == "a":  # (i, j) sends to (i, (j - i) % P)
                pairs.append((i * pg + j, i * pg + ((j - i) % pg)))
            else:  # b: (i, j) sends to ((i - j) % P, j)
                pairs.append((i * pg + j, ((i - j) % pg) * pg + j))
    return pairs


def _shift_perm(pg: int):
    """Single-axis circular shift by one (left/up)."""
    return [(k, (k - 1) % pg) for k in range(pg)]


def build_cannon_schedule(
    pg: int,
    *,
    row_axis: str,
    col_axis: str,
    skew: bool = True,
    steps: Optional[int] = None,
    step_offset: int = 0,
    empty_steps: frozenset = frozenset(),
    local_shape: Optional[tuple] = None,
    itemsize: int = 4,
) -> Schedule:
    """Schedule for Cannon's algorithm on a ``pg`` x ``pg`` grid.

    ``steps`` / ``step_offset`` support the 2.5D variant (cannon25d.py)
    where each replica executes a strided/offset subset of the shifts.
    ``local_shape`` = (ml, kl, nl) of the per-device multiply fills the
    observability byte counts (the callables never need it).
    """
    n_steps = pg if steps is None else steps
    shift_a = _shift_perm(pg)
    shift_b = _shift_perm(pg)

    def prologue(a_blk, b_blk):
        if skew:
            a_blk = jax.lax.ppermute(a_blk, (row_axis, col_axis),
                                     _skew_perm(pg, "a"))
            b_blk = jax.lax.ppermute(b_blk, (row_axis, col_axis),
                                     _skew_perm(pg, "b"))
        if step_offset:
            # jump the k-phase forward by step_offset (2.5D replica offset)
            off_a = [(j, (j - step_offset) % pg) for j in range(pg)]
            off_b = [(i, (i - step_offset) % pg) for i in range(pg)]
            a_blk = jax.lax.ppermute(a_blk, col_axis, off_a)
            b_blk = jax.lax.ppermute(b_blk, row_axis, off_b)
        return (a_blk, b_blk)

    def shift(carry, t):
        a_blk, b_blk = carry
        return (jax.lax.ppermute(a_blk, col_axis, shift_a),
                jax.lax.ppermute(b_blk, row_axis, shift_b))

    def rolled_shift(carry):
        return shift(carry, 0)

    step_bytes = 0
    prologue_bytes = 0
    if local_shape is not None:
        ml, kl, nl = local_shape
        step_bytes = (ml * kl + kl * nl) * itemsize
        # a one-chip grid's skew is a permutation onto itself
        prologue_bytes = step_bytes if (skew or step_offset) and pg > 1 \
            else 0

    return Schedule(
        algorithm="cannon",
        n_steps=n_steps,
        prologue=prologue,
        shift=shift,
        empty_steps=frozenset(empty_steps),
        rolled=RolledSpec(shift=rolled_shift),
        comm_op=f"ppermute(a:{col_axis}, b:{row_axis})",
        prologue_comm_bytes=prologue_bytes,
        # whole-block ppermutes act on A's rows and B's columns alone;
        # a one-chip grid's prologue moves nothing
        row_col_panels=bool(skew or step_offset) and pg > 1,
        # the final step receives no shift: n_steps - 1 shifts total
        step_comm_bytes=tuple(
            step_bytes if t + 1 < n_steps else 0 for t in range(n_steps)),
    )


@functools.lru_cache(maxsize=256)
def cannon_step_map(nbr: int, nbk: int, nbc: int, pg: int,
                    c_repl: int = 1) -> StepMap:
    """The step map (core/steps.py) of (2.5D) Cannon on a ``pg`` x
    ``pg`` grid over an (nbr, nbk, nbc) block grid: at inner step t,
    rank ``(p*pg + i)*pg + j`` (replica p of ``c_repl``; plain Cannon is
    ``p = 0``) holds A chunk (i, q) and B chunk (q, j) with
    ``q = (i + j + p*spr + t) % pg``, ``spr = pg // c_repl`` the steps
    each replica runs — where the skew (``_skew_perm``, or
    ``cannon25d._skew25d_perm``) and t shifts (``_shift_perm``) bring
    them."""
    if nbr % pg or nbk % pg or nbc % pg:
        raise ValueError(
            f"block grid ({nbr},{nbk},{nbc}) not divisible by cannon grid "
            f"side {pg}")
    if c_repl < 1 or pg % c_repl:
        raise ValueError(f"grid side {pg} not divisible by replication {c_repl}")
    lr, lk, lc = nbr // pg, nbk // pg, nbc // pg
    spr = pg // c_repl
    return StepMap.of(
        [[(i * lr, (i + j + p * spr + t) % pg * lk, j * lc)
          for p in range(c_repl) for i in range(pg) for j in range(pg)]
         for t in range(spr)],
        (lr, lk, lc), pair=True)


def _default_local_matmul(precision):
    def f(a, b):
        return jax.lax.dot(a, b, precision=precision,
                           preferred_element_type=jnp.float32)

    return f


def cannon_matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    mesh: jax.sharding.Mesh,
    grid: GridSpec = GridSpec(),
    local_matmul: Optional[Callable] = None,
    out_dtype=None,
    precision=jax.lax.Precision.DEFAULT,
    pipeline_depth: Optional[int] = None,
    double_buffer: Optional[bool] = None,
    skew: bool = True,
) -> jax.Array:
    """C = A @ B with Cannon's algorithm on a square (row, col) grid.

    A: (M, K) sharded P(row_axis, col_axis)
    B: (K, N) sharded P(row_axis, col_axis)
    C: (M, N) sharded P(row_axis, col_axis)

    Per-device communication volume: (M*K + K*N) / P * sqrt(P) total
    over sqrt(P) steps == O(1/sqrt(P)) of the matrix size, the paper's
    scaling for general shapes.

    ``pipeline_depth`` (see core/schedule.py): 2 = double-buffered
    comm/compute overlap (default), 1 = serial, 0 = rolled fori_loop
    ablation.  ``double_buffer`` is the legacy spelling (True -> 2,
    False -> 0); ``pipeline_depth`` wins when both are given.
    """
    pg = grid.validate_square(mesh)
    if out_dtype is None:
        out_dtype = jnp.promote_types(a.dtype, b.dtype)
    lm = local_matmul or _default_local_matmul(precision)
    depth = resolve_pipeline_depth(pipeline_depth, double_buffer)
    sched = build_cannon_schedule(
        pg, row_axis=grid.row_axis, col_axis=grid.col_axis, skew=skew,
        empty_steps=getattr(lm, "empty_steps", frozenset()))

    def body(a_blk, b_blk):
        return execute_schedule(sched, a_blk, b_blk, local_matmul=lm,
                                out_dtype=out_dtype, pipeline_depth=depth)

    # leading batch dims (a fused product batch (G, m, k)) replicate;
    # the ppermute skew/shift callables are shape-agnostic, so the same
    # schedule drives single products and batches alike
    spec = P(*([None] * (a.ndim - 2)), grid.row_axis, grid.col_axis)
    fn = jax.shard_map(body, mesh=mesh, in_specs=(spec, spec),
                       out_specs=spec, check_vma=False)
    return fn(a, b)
