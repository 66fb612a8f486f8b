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
issued before the local multiply of panel t.
"""
from __future__ import annotations

import math
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .blocking import GridSpec
from .cannon import _default_local_matmul
from .schedule import Schedule, execute_schedule, resolve_pipeline_depth

__all__ = ["summa_matmul", "summa_n_panels", "build_summa_schedule",
           "build_summa_gather_schedule", "summa_step_masks",
           "summa_gather_masks", "summa_step_norms", "summa_gather_norms",
           "summa_rank_steps", "summa_gather_rank_steps"]


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


def summa_step_masks(
    am: np.ndarray, bm: np.ndarray, pr: int, pc: int, n_panels: int,
) -> List[Tuple[np.ndarray, np.ndarray]]:
    """Per-panel (a_mask, b_mask) unions for psum-broadcast SUMMA — the
    schedule builder's per-step mask slices.

    Panel p covers the global K block range [p*nbk/n_panels, ...); the
    A-side union runs over the pr row chunks, the B-side over the pc
    column chunks.  Because the row and column ranks vary independently,
    the union of per-rank products equals the product of the factored
    unions — no 3D pair tensor needed.
    """
    nbr, nbk = am.shape
    nbc = bm.shape[1]
    if nbr % pr or nbc % pc or nbk % n_panels:
        raise ValueError(
            f"block grid ({nbr},{nbk},{nbc}) not divisible by summa grid "
            f"{pr}x{pc} with {n_panels} panels")
    lr, lc, lkp = nbr // pr, nbc // pc, nbk // n_panels
    out = []
    for p in range(n_panels):
        ksl = slice(p * lkp, (p + 1) * lkp)
        ua = np.zeros((lr, lkp), dtype=bool)
        for i in range(pr):
            ua |= am[i * lr:(i + 1) * lr, ksl]
        ub = np.zeros((lkp, lc), dtype=bool)
        for j in range(pc):
            ub |= bm[ksl, j * lc:(j + 1) * lc]
        out.append((ua, ub))
    return out


def summa_step_norms(
    an: np.ndarray, bn: np.ndarray, pr: int, pc: int, n_panels: int,
) -> List[Tuple[np.ndarray, np.ndarray]]:
    """Per-panel (a_norms, b_norms) max-unions for psum-broadcast SUMMA
    — the norm twin of ``summa_step_masks`` (repro.sparsity).

    SPMD union-of-max semantics: the A-side takes the elementwise MAX
    over the pr row chunks, the B-side over the pc column chunks.  The
    factored product ``max_i(an) * max_j(bn)`` upper-bounds every
    rank's norm product, so ``filter_eps`` never drops a triple some
    rank still needs — the same conservativeness as the factored mask
    union."""
    nbr, nbk = an.shape
    nbc = bn.shape[1]
    if nbr % pr or nbc % pc or nbk % n_panels:
        raise ValueError(
            f"block grid ({nbr},{nbk},{nbc}) not divisible by summa grid "
            f"{pr}x{pc} with {n_panels} panels")
    an = np.asarray(an, dtype=np.float32)
    bn = np.asarray(bn, dtype=np.float32)
    lr, lc, lkp = nbr // pr, nbc // pc, nbk // n_panels
    out = []
    for p in range(n_panels):
        ksl = slice(p * lkp, (p + 1) * lkp)
        ua = np.zeros((lr, lkp), dtype=np.float32)
        for i in range(pr):
            np.maximum(ua, an[i * lr:(i + 1) * lr, ksl], out=ua)
        ub = np.zeros((lkp, lc), dtype=np.float32)
        for j in range(pc):
            np.maximum(ub, bn[ksl, j * lc:(j + 1) * lc], out=ub)
        out.append((ua, ub))
    return out


def summa_gather_norms(
    an: np.ndarray, bn: np.ndarray, pr: int, pc: int,
) -> Tuple[np.ndarray, np.ndarray]:
    """Factored max-unions for PUMMA-style (all-gather) SUMMA — the
    norm twin of ``summa_gather_masks``: one step, A maxed over row
    chunks, B over column chunks."""
    nbr, nbk = an.shape
    nbc = bn.shape[1]
    if nbr % pr or nbc % pc:
        raise ValueError(
            f"block grid ({nbr},{nbc}) not divisible by grid {pr}x{pc}")
    an = np.asarray(an, dtype=np.float32)
    bn = np.asarray(bn, dtype=np.float32)
    lr, lc = nbr // pr, nbc // pc
    ua = np.zeros((lr, nbk), dtype=np.float32)
    for i in range(pr):
        np.maximum(ua, an[i * lr:(i + 1) * lr], out=ua)
    ub = np.zeros((nbk, lc), dtype=np.float32)
    for j in range(pc):
        np.maximum(ub, bn[:, j * lc:(j + 1) * lc], out=ub)
    return ua, ub


def summa_gather_masks(
    am: np.ndarray, bm: np.ndarray, pr: int, pc: int,
) -> Tuple[np.ndarray, np.ndarray]:
    """Factored unions for PUMMA-style (all-gather) SUMMA: the local
    multiply sees the full K extent, so there is a single step whose A
    mask unions over row chunks and B mask over column chunks."""
    nbr, nbk = am.shape
    nbc = bm.shape[1]
    if nbr % pr or nbc % pc:
        raise ValueError(
            f"block grid ({nbr},{nbc}) not divisible by grid {pr}x{pc}")
    lr, lc = nbr // pr, nbc // pc
    ua = np.zeros((lr, nbk), dtype=bool)
    for i in range(pr):
        ua |= am[i * lr:(i + 1) * lr]
    ub = np.zeros((nbk, lc), dtype=bool)
    for j in range(pc):
        ub |= bm[:, j * lc:(j + 1) * lc]
    return ua, ub


def summa_rank_steps(
    am: np.ndarray, bm: np.ndarray, pr: int, pc: int, n_panels: int,
    a_norms: Optional[np.ndarray] = None,
    b_norms: Optional[np.ndarray] = None,
) -> List[List[dict]]:
    """Rank-exact twin of ``summa_step_masks``/``summa_step_norms``:
    per panel, per RANK exact local mask (and norm) kwargs.

    ``out[p][r]`` is the kwarg dict for rank ``r = i * pc + j`` at
    panel ``p`` — its own A row chunk against the panel's K slice and
    the panel's K slice against its own B column chunk, no cross-rank
    union and no union-of-max norms.
    """
    nbr, nbk = am.shape
    nbc = bm.shape[1]
    if nbr % pr or nbc % pc or nbk % n_panels:
        raise ValueError(
            f"block grid ({nbr},{nbk},{nbc}) not divisible by summa grid "
            f"{pr}x{pc} with {n_panels} panels")
    lr, lc, lkp = nbr // pr, nbc // pc, nbk // n_panels
    if a_norms is not None:
        a_norms = np.asarray(a_norms, dtype=np.float32)
        b_norms = np.asarray(b_norms, dtype=np.float32)
    steps: List[List[dict]] = []
    for p in range(n_panels):
        ksl = slice(p * lkp, (p + 1) * lkp)
        ranks: List[dict] = []
        for i in range(pr):
            rs = slice(i * lr, (i + 1) * lr)
            for j in range(pc):
                cs = slice(j * lc, (j + 1) * lc)
                kw = {"a_mask": am[rs, ksl], "b_mask": bm[ksl, cs]}
                if a_norms is not None:
                    kw["a_norms"] = a_norms[rs, ksl]
                    kw["b_norms"] = b_norms[ksl, cs]
                ranks.append(kw)
        steps.append(ranks)
    return steps


def summa_gather_rank_steps(
    am: np.ndarray, bm: np.ndarray, pr: int, pc: int,
    a_norms: Optional[np.ndarray] = None,
    b_norms: Optional[np.ndarray] = None,
) -> List[dict]:
    """Rank-exact twin of ``summa_gather_masks``/``summa_gather_norms``
    for the single-step all-gather variant: rank ``r = i * pc + j``
    multiplies its exact A row chunk (full K) by its exact B column
    chunk."""
    nbr, nbk = am.shape
    nbc = bm.shape[1]
    if nbr % pr or nbc % pc:
        raise ValueError(
            f"block grid ({nbr},{nbc}) not divisible by grid {pr}x{pc}")
    lr, lc = nbr // pr, nbc // pc
    if a_norms is not None:
        a_norms = np.asarray(a_norms, dtype=np.float32)
        b_norms = np.asarray(b_norms, dtype=np.float32)
    ranks: List[dict] = []
    for i in range(pr):
        rs = slice(i * lr, (i + 1) * lr)
        for j in range(pc):
            cs = slice(j * lc, (j + 1) * lc)
            kw = {"a_mask": am[rs], "b_mask": bm[:, cs]}
            if a_norms is not None:
                kw["a_norms"] = a_norms[rs]
                kw["b_norms"] = b_norms[:, cs]
            ranks.append(kw)
    return ranks


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
