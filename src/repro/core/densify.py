"""Densification — the paper's core optimization (section III).

DBCSR stores operands as many small blocks.  For *dense* inputs the
per-thread blocks are coalesced ("densified") into one large dense
block, so that:
  1. the Generation phase has fewer blocks to organise into stacks,
  2. the Scheduler phase has fewer stacks to handle (stack size -> 1),
  3. the local multiply becomes a single large GEMM executed by the
     vendor library (cuBLAS there, the MXU dot / tiled_matmul Pallas
     kernel here), which is where large-block throughput saturates.

The cost is the densify/undensify copy of the payload (the paper's
measured overhead).  On TPU the copies are pure layout transforms
((nbr, nbc, bm, bn) <-> (nbr*bm, nbc*bn) reshuffles) that XLA fuses
into surrounding ops; the *performance* content of the trade-off
(many small dots vs one big dot) is identical and is what
benchmarks/bench_densify.py measures.

This module provides the layout transforms plus the two local-multiply
strategies consumed by cannon/summa/tall_skinny's ``local_matmul`` hook:

  * ``densified_local_matmul`` — densify, one big dot, undensify.
  * ``blocked_local_matmul``   — keep blocks, run the stack plans
    through the smm kernel (LIBCUSMM analogue) or its jnp reference.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

__all__ = [
    "to_blocks",
    "from_blocks",
    "to_blocks_batched",
    "from_blocks_batched",
    "densify",
    "undensify",
    "blocked_local_matmul",
    "densified_local_matmul",
    "grouped_densified_local_matmul",
]


def to_blocks(x: jax.Array, bm: int, bn: int) -> jax.Array:
    """(R, C) -> (nbr*nbc, bm, bn) stacked blocks, row-major block order.

    This is the 'blocked' storage: the DBCSR payload of a dense matrix.
    """
    r, c = x.shape
    if r % bm or c % bn:
        raise ValueError(f"shape {x.shape} not divisible by block ({bm},{bn})")
    nbr, nbc = r // bm, c // bn
    return (
        x.reshape(nbr, bm, nbc, bn).transpose(0, 2, 1, 3).reshape(nbr * nbc, bm, bn)
    )


def from_blocks(blocks: jax.Array, nbr: int, nbc: int) -> jax.Array:
    """Inverse of to_blocks."""
    _, bm, bn = blocks.shape
    return (
        blocks.reshape(nbr, nbc, bm, bn).transpose(0, 2, 1, 3).reshape(nbr * bm, nbc * bn)
    )


def to_blocks_batched(x: jax.Array, bm: int, bn: int) -> jax.Array:
    """(G, R, C) -> (G, nbr*nbc, bm, bn): ``to_blocks`` over a leading
    product/group dimension (the fused batched multiply's payload)."""
    g, r, c = x.shape
    if r % bm or c % bn:
        raise ValueError(f"shape {x.shape} not divisible by block ({bm},{bn})")
    nbr, nbc = r // bm, c // bn
    return (
        x.reshape(g, nbr, bm, nbc, bn)
        .transpose(0, 1, 3, 2, 4)
        .reshape(g, nbr * nbc, bm, bn)
    )


def from_blocks_batched(blocks: jax.Array, nbr: int, nbc: int) -> jax.Array:
    """Inverse of to_blocks_batched."""
    g, _, bm, bn = blocks.shape
    return (
        blocks.reshape(g, nbr, nbc, bm, bn)
        .transpose(0, 1, 3, 2, 4)
        .reshape(g, nbr * bm, nbc * bn)
    )


def densify(blocks: jax.Array, nbr: int, nbc: int) -> jax.Array:
    """Coalesce a blocked payload into one dense block (paper eq. 1/2).

    In DBCSR this is a copy into fresh memory-pool buffers; here it is
    the layout transform from block-stacked to contiguous row-major.
    """
    return from_blocks(blocks, nbr, nbc)


def undensify(dense: jax.Array, bm: int, bn: int) -> jax.Array:
    """Decompose the densified C back into the original block sizes."""
    return to_blocks(dense, bm, bn)


def densified_local_matmul(precision=jax.lax.Precision.DEFAULT,
                           kernel: Optional[str] = None):
    """Local multiply for the densified path: one large GEMM.

    kernel=None     -> jax.lax.dot (XLA's MXU path; the 'vendor' GEMM)
    kernel='pallas' -> kernels/tiled_matmul (explicit VMEM tiling)
    """
    if kernel == "pallas":
        from repro.kernels.tiled_matmul.ops import tiled_matmul

        def f(a, b):
            return tiled_matmul(a, b)

        return f

    def f(a, b):
        # a stable name for the dot's device op in a profiler trace
        with jax.named_scope("dbcsr.local_dot"):
            return jax.lax.dot(a, b, precision=precision,
                               preferred_element_type=jnp.float32)

    # a product of A's row panels and B's column panels is that block
    # of the product, so the schedule may run it on row/column panels
    # (schedule.skew_panels)
    f.row_col_panels = True
    return f


def grouped_densified_local_matmul(precision=jax.lax.Precision.DEFAULT,
                                   kernel: Optional[str] = None):
    """Local multiply for the densified path of a fused product batch:
    one grouped GEMM over ``(G, ml, kl) @ (G, kl, nl)``.

    kernel=None     -> batched jax.lax.dot_general (XLA's MXU path)
    kernel='pallas' -> kernels/grouped_gemm (one Pallas dispatch for
                       all G products — the grouped-GEMM unification)
    """
    if kernel == "pallas":
        from repro.kernels.grouped_gemm.ops import grouped_gemm

        def f(a, b):
            return grouped_gemm(a, b)

        return f

    def f(a, b):
        return jax.lax.dot_general(
            a, b, (((a.ndim - 1,), (1,)), ((0,), (0,))),
            precision=precision, preferred_element_type=jnp.float32)

    return f


def blocked_local_matmul(
    m: int,
    k: int,
    n: int,
    *,
    block_m: int,
    block_k: int,
    block_n: int,
    stack_size: Optional[int] = None,
    align: Optional[bool] = None,
    kernel: str = "smm",
    a_mask=None,
    b_mask=None,
    pair_mask=None,
    a_norms=None,
    b_norms=None,
    pair_norms=None,
    filter_eps: Optional[float] = None,
    stack_bins: Optional[int] = None,
):
    """Local multiply for the blocked path.

    Delegates to the fused stack executor (core/engine.py): one memoized
    plan build per geometry (and per occupancy-mask/norm fingerprint),
    one ``lax.scan`` over padded stacks, one smm trace per block
    geometry.  ``stack_size`` / ``align`` default to the autotune
    winners table for this block geometry and occupancy bin;
    ``stack_bins`` caps the executor's size-bin count (None: the
    DBCSR_STACK_BINS env or 4).  Occupancy masks
    (``a_mask``/``b_mask``/``pair_mask``, host-side numpy bool) restrict
    the plan to present triples — see the sparse planning contract in
    core/engine.py — and block norms + ``filter_eps`` apply DBCSR's
    norm-product on-the-fly filter on top (repro.sparsity).

    kernel='smm'  -> Pallas LIBCUSMM-analogue (interpret-mode on CPU)
    kernel='ref'  -> pure-jnp gather/segment-sum oracle (same math)
    """
    from .engine import stack_executor

    return stack_executor(
        m, k, n, block_m=block_m, block_k=block_k, block_n=block_n,
        stack_size=stack_size, align=align, kernel=kernel,
        a_mask=a_mask, b_mask=b_mask, pair_mask=pair_mask,
        a_norms=a_norms, b_norms=b_norms, pair_norms=pair_norms,
        filter_eps=filter_eps, stack_bins=stack_bins,
    )
