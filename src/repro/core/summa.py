"""SUMMA — the ScaLAPACK PDGEMM-style baseline DBCSR is compared against.

The paper's headline result (section IV-C) is densified DBCSR vs the
PDGEMM of Cray LibSci_acc, a GPU-accelerated ScaLAPACK.  ScaLAPACK's
PDGEMM is SUMMA-like: for each panel k of the contraction dimension,
the owning column of the process grid broadcasts its A panel along
rows, the owning row broadcasts its B panel along columns, and every
process accumulates a local GEMM.

We implement the panel broadcast two ways:

  * ``bcast='psum'``   — masked all-reduce per panel.  One-shot,
    latency-light, but moves ~2x the optimal broadcast volume.  This is
    the *baseline* configuration: its extra volume vs Cannon is what
    the roofline comparison in benchmarks/bench_vs_pgemm.py surfaces
    (the in-framework analogue of the paper's Fig. 4).
  * ``bcast='gather'`` — one all-gather of all panels up front (PUMMA
    style); volume-optimal broadcast, memory cost sqrt(P)x local
    operand size.

Unlike Cannon, SUMMA supports non-square process grids.

The panel loop is the unified schedule engine (core/schedule.py):
``build_summa_schedule`` emits one step per panel whose ``recv`` is the
masked-allreduce broadcast (operands stay resident — ``shift`` is the
identity), so at ``pipeline_depth=2`` the broadcast of panel t+1 is
issued before the local multiply of panel t.  ``summa_step_map`` says
which blocks each rank multiplies at each panel (one panel of full K
for the all-gather); the blocked path plans its stacks from it
(core/steps.py).
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .blocking import GridSpec
from .cannon import _default_local_matmul
from .schedule import Schedule, execute_schedule, resolve_pipeline_depth
from .steps import StepMap

__all__ = ["summa_matmul", "summa_n_panels", "build_summa_schedule",
           "build_summa_gather_schedule", "summa_step_map"]


def summa_n_panels(pr: int, pc: int) -> int:
    """Contraction panel count of the psum-broadcast SUMMA on a (pr, pc)
    grid: one panel per grid column of A for square grids; the lcm for
    non-square so both the A column owner and the B row owner of every
    panel are well defined.  Exported so the blocked local-multiply
    planner (core/multiply.py) sizes per-panel stack plans consistently.
    """
    return pc if pr == pc else math.lcm(pr, pc)


def build_summa_schedule(
    pr: int,
    pc: int,
    *,
    row_axis: str,
    col_axis: str,
    n_panels: Optional[int] = None,
    empty_steps: frozenset = frozenset(),
    local_shape: Optional[tuple] = None,
    itemsize: int = 4,
) -> Schedule:
    """Schedule for psum-broadcast SUMMA: one step per contraction
    panel; ``recv`` slices the resident local blocks and broadcasts the
    panel pair by masked all-reduce along the perpendicular grid axes.
    """
    n_panels = summa_n_panels(pr, pc) if n_panels is None else n_panels

    def recv(carry, p):
        a_blk, b_blk = carry
        # K is the LAST axis of A and second-to-last of B so the slices
        # are agnostic to leading batch dims ((ml, kl) single product,
        # (G, ml, kl) fused product batch)
        kl_a = a_blk.shape[-1] * pc // n_panels  # A panel width (local)
        kl_b = b_blk.shape[-2] * pr // n_panels  # B panel height (local)
        my_col = jax.lax.axis_index(col_axis)
        my_row = jax.lax.axis_index(row_axis)
        # owner coordinates of panel p
        col_owner = p * pc // n_panels
        row_owner = p * pr // n_panels
        a_off = (p % (n_panels // pc)) * kl_a if n_panels != pc else 0
        b_off = (p % (n_panels // pr)) * kl_b if n_panels != pr else 0
        a_panel = jax.lax.dynamic_slice_in_dim(a_blk, a_off, kl_a,
                                               axis=a_blk.ndim - 1)
        b_panel = jax.lax.dynamic_slice_in_dim(b_blk, b_off, kl_b,
                                               axis=b_blk.ndim - 2)
        # broadcast-by-masked-allreduce along the perpendicular axis
        a_panel = jnp.where(my_col == col_owner, a_panel, 0)
        a_panel = jax.lax.psum(a_panel, col_axis)
        b_panel = jnp.where(my_row == row_owner, b_panel, 0)
        b_panel = jax.lax.psum(b_panel, row_axis)
        return (a_panel, b_panel)

    step_bytes = 0
    if local_shape is not None:
        ml, klp, nl = local_shape  # per-panel local multiply geometry
        # masked all-reduce moves ~2x the optimal broadcast volume
        step_bytes = 2 * (ml * klp + klp * nl) * itemsize

    return Schedule(
        algorithm="summa",
        n_steps=n_panels,
        recv=recv,
        empty_steps=frozenset(empty_steps),
        comm_op=f"bcast-psum(a:{col_axis}, b:{row_axis})",
        step_comm_bytes=tuple(
            0 if t in empty_steps else step_bytes for t in range(n_panels)),
    )


@functools.lru_cache(maxsize=256)
def summa_step_map(nbr: int, nbk: int, nbc: int, pr: int, pc: int,
                   n_panels: int) -> StepMap:
    """The step map (core/steps.py) of SUMMA on a (pr, pc) grid over an
    (nbr, nbk, nbc) block grid: at panel p, rank ``i*pc + j`` multiplies
    its A row chunk i and B column chunk j over the panel's K range
    ``[p*nbk/n_panels, (p+1)*nbk/n_panels)``.  The all-gather broadcast
    is the map of one panel: every rank sees the full K range."""
    if nbr % pr or nbc % pc or nbk % n_panels:
        raise ValueError(
            f"block grid ({nbr},{nbk},{nbc}) not divisible by summa grid "
            f"{pr}x{pc} with {n_panels} panels")
    lr, lc, lkp = nbr // pr, nbc // pc, nbk // n_panels
    return StepMap.of(
        [[(i * lr, p * lkp, j * lc) for i in range(pr) for j in range(pc)]
         for p in range(n_panels)],
        (lr, lkp, lc), pair=False)


def build_summa_gather_schedule(row_axis: str, col_axis: str,
                           local_shape: Optional[tuple] = None,
                           itemsize: int = 4) -> Schedule:
    """PUMMA-style SUMMA as a single-step schedule: the all-gather of
    the full local row of A / column of B is the prologue, the one
    local multiply is step 0."""

    def prologue(a_blk, b_blk):
        a_row = jax.lax.all_gather(a_blk, col_axis, axis=1, tiled=True)
        b_col = jax.lax.all_gather(b_blk, row_axis, axis=0, tiled=True)
        return (a_row, b_col)

    prologue_bytes = 0
    if local_shape is not None:
        ml, kl, nl = local_shape  # gathered (full-K) local geometry
        prologue_bytes = (ml * kl + kl * nl) * itemsize

    return Schedule(
        algorithm="summa",
        n_steps=1,
        prologue=prologue,
        comm_op=f"all_gather(a:{col_axis}, b:{row_axis})",
        prologue_comm_bytes=prologue_bytes,
    )


def summa_matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    mesh: jax.sharding.Mesh,
    grid: GridSpec = GridSpec(),
    local_matmul: Optional[Callable] = None,
    out_dtype=None,
    precision=jax.lax.Precision.DEFAULT,
    bcast: str = "psum",
    pipeline_depth: Optional[int] = None,
    double_buffer: Optional[bool] = None,
) -> jax.Array:
    """C = A @ B via SUMMA on the (row_axis, col_axis) grid.

    ``pipeline_depth`` follows core/schedule.py semantics: at depth 2
    the panel broadcast for step t+1 overlaps the local multiply of
    step t; depth 1 is strictly serial (bit-identical output).
    """
    pr, pc = grid.grid_shape(mesh)
    if out_dtype is None:
        out_dtype = jnp.promote_types(a.dtype, b.dtype)
    lm = local_matmul or _default_local_matmul(precision)
    depth = resolve_pipeline_depth(pipeline_depth, double_buffer)

    if bcast == "gather":
        # the single gathered dot historically cast straight to
        # out_dtype — accumulate there, not in f32, so f64/int operands
        # keep full precision
        sched = build_summa_gather_schedule(grid.row_axis, grid.col_axis)
        accum = out_dtype
    elif bcast == "psum":
        sched = build_summa_schedule(
            pr, pc, row_axis=grid.row_axis, col_axis=grid.col_axis,
            empty_steps=getattr(lm, "empty_steps", frozenset()))
        accum = jnp.float32  # legacy per-panel f32 accumulation
    else:
        raise ValueError(bcast)

    def body(a_blk, b_blk):
        return execute_schedule(sched, a_blk, b_blk, local_matmul=lm,
                                out_dtype=out_dtype, pipeline_depth=depth,
                                accum_dtype=accum)

    # leading batch dims (a fused product batch (G, m, k)) replicate;
    # the trailing two axes shard over the process grid as always
    spec = P(*([None] * (a.ndim - 2)), grid.row_axis, grid.col_axis)
    fn = jax.shard_map(body, mesh=mesh, in_specs=(spec, spec),
                       out_specs=spec, check_vma=False)
    return fn(a, b)
