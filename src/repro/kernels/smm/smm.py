"""Pallas small-matrix-multiply stack kernel — LIBCUSMM's TPU analogue.

LIBCUSMM processes *stacks* of small-block multiplications
C[c] += A[a] @ B[b] with JIT-generated CUDA kernels parametrised over
threads/block, per-thread work, and tiling (paper section II).  None of
those CUDA dimensions exist on TPU; the TPU-native parameter space is:

  * BlockSpec block shapes (how much of each operand lives in VMEM),
  * MXU alignment padding (the systolic array wants multiples of
    (8, 128) lanes; small DBCSR blocks of 22/64 are padded by ops.py),
  * the grid layout (one grid step per stack entry, scalar-prefetched
    indices, flattened so a 30,000-entry stack fits in SMEM).

The stack's (a, b, c) indices are data: they drive *which* blocks each
grid step touches.  That requires scalar prefetch
(pltpu.PrefetchScalarGridSpec) so the index_map can read them before
the DMA of the corresponding blocks is issued.

Triples may carry an optional 4th column (validity mask).  The fused
stack executor (core/engine.py) pads ragged stacks to a uniform tile;
padding rows have mask 0 and point ``c_idx`` at a scratch block one
past the real C blocks, so their (zeroed) products never touch real
output.

Accumulation correctness relies on the stack invariant established by
stacks.py: entries with equal c_idx are contiguous, so each C block is
resident in VMEM for exactly one run of consecutive grid steps (the
TPU output-revisit rule).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["smm_pallas_call"]


def _smm_kernel(triples_ref, a_ref, b_ref, c_in_ref, c_out_ref, *, width):
    s = pl.program_id(0)
    row = width * s  # entry s occupies triples_ref[row : row + width]
    # first grid step of this C block's contiguous run?
    prev_same = jnp.where(
        s > 0,
        triples_ref[jnp.maximum(row - width, 0) + 2] == triples_ref[row + 2],
        False,
    )
    prod = jnp.dot(
        a_ref[0].astype(jnp.float32),
        b_ref[0].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    if width > 3:
        # masked triples (fused-executor stack padding): column 3 is a
        # validity flag — zero the padding entries' product so their
        # accumulation into the scratch C block is a no-op.
        prod = prod * triples_ref[row + 3].astype(jnp.float32)

    @pl.when(jnp.logical_not(prev_same))
    def _init():  # start of run: seed with the incoming C block
        c_out_ref[0] = c_in_ref[0] + prod

    @pl.when(prev_same)
    def _accum():  # same C block as previous step: VMEM-resident add
        c_out_ref[0] = c_out_ref[0] + prod


@functools.partial(jax.jit, static_argnames=("interpret",))
def smm_pallas_call(
    a_blocks: jax.Array,  # (Na, bm, bk)
    b_blocks: jax.Array,  # (Nb, bk, bn)
    c_blocks: jax.Array,  # (Nc, bm, bn) float32
    triples: jax.Array,   # (S, 3|4) int32, c-runs contiguous
    *,
    interpret: bool = False,
) -> jax.Array:
    s_len, width = triples.shape
    _, bm, bk = a_blocks.shape
    _, bk2, bn = b_blocks.shape
    assert bk == bk2, (a_blocks.shape, b_blocks.shape)

    # Scalar-prefetch the triples flattened to (width * S,): SMEM pads
    # the minor dim of a 2-D operand to 128 lanes (512 B per entry), so
    # an (S, 4) operand outgrows v5e's 1 MiB of SMEM at ~2,000 entries,
    # while the flat layout costs 16 B per entry (~65,000 entries).
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(s_len,),
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda s, t: (t[width * s], 0, 0)),
            pl.BlockSpec((1, bk, bn), lambda s, t: (t[width * s + 1], 0, 0)),
            pl.BlockSpec((1, bm, bn), lambda s, t: (t[width * s + 2], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn),
                               lambda s, t: (t[width * s + 2], 0, 0)),
    )
    return pl.pallas_call(
        functools.partial(_smm_kernel, width=width),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(c_blocks.shape, jnp.float32),
        input_output_aliases={3: 0},  # c_blocks buffer is donated to out
        interpret=interpret,
        name="smm",
    )(triples.reshape(-1), a_blocks, b_blocks, c_blocks)
