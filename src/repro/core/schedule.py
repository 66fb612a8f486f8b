"""Pipelined schedule engine — the single step-loop driver behind every
data-exchange algorithm.

The paper's headline GPU win comes from overlapping inter-rank transfer
with local stack processing: the async transfer of the *next* Cannon
shift is issued while the GPU consumes the *current* stacks (MPI/CUDA-
stream double buffering).  The 2.5D companion paper (Lazzaro et al.,
arXiv:1705.10218) and the batched distributed-GPU work of Mijić &
Davidović (arXiv:2203.09353) both show the pipelining structure is
algorithm-independent — so it lives here once, instead of in four
hand-rolled loops.

Contract
--------

Each algorithm module exports a pure *schedule builder* that returns a
``Schedule``: a host-side description of the step sequence

  * ``prologue(a, b) -> carry``      one-time setup comm (Cannon skew,
                                     2.5D replica-offset skew, PUMMA
                                     all-gather); identity by default
  * ``recv(carry, t) -> (a_t, b_t)`` the communication producing step
                                     ``t``'s compute operands (SUMMA's
                                     panel broadcast; identity for
                                     Cannon, whose carry IS the operand
                                     pair)
  * ``shift(carry, t) -> carry``     the carry update feeding step
                                     ``t + 1`` (Cannon's neighbour
                                     ppermute; identity for SUMMA — its
                                     operands stay resident)
  * ``epilogue(c) -> c``             post-loop collective (2.5D stack
                                     reduction, tall-skinny reduce)

plus static metadata: ``n_steps``, the host-static ``empty_steps`` set
(steps whose occupancy-mask product is empty on every rank — SPMD-safe
to skip because it is uniform across devices; under rank-exact
execution this is the ALL-ranks-empty intersection, which equals the
union plan's emptiness because the max norm product over ranks clears
``filter_eps`` iff some rank retains a triple — so the comm schedule
is identical whether the local multiply runs union or per-rank plans),
per-step ``comm_op`` labels and ``step_comm_bytes`` estimates for
observability, and an optional ``rolled`` spec for the fori_loop
ablation form.

``execute_schedule`` runs any schedule with software double-buffering:

  pipeline_depth = 2   the ``shift``/``recv`` for step ``t + 1`` is
                       issued against a second buffer *before* step
                       ``t``'s local multiply, so XLA schedules the
                       collective-permute-start/done (or broadcast)
                       around the compute — the paper's comm/compute
                       overlap.  This is the default.
  pipeline_depth = 1   strictly serial: all communication for step
                       ``t + 1`` is issued after step ``t``'s multiply.
                       Bit-identical output (the same float ops on the
                       same values in the same accumulation order);
                       only the issue order — and therefore the
                       overlap — changes.  Where depth 2 panels the
                       prologue (below), it is allclose to depth 1.
  pipeline_depth = 0   rolled ``fori_loop`` form (smaller HLO, no
                       overlap) where the schedule provides a ``rolled``
                       spec; falls back to depth 1 otherwise.  Kept for
                       the HLO-size ablation (the legacy
                       ``double_buffer=False``).

Panelled prologue (depth 2): Cannon's skew is a transfer that step 0's
multiply waits on, and no double buffering reaches it.  Where the
schedule's prologue moves bytes between chips and, like its shifts and
receives, acts on A's rows and B's columns alone (``Schedule.
row_col_panels``: Cannon, Cannon-2.5D), the local multiply is a plain
product (``local_matmul.row_col_panels``: the densified dot) and the
local blocks are large enough (``skew_panels``), ``execute_schedule``
runs the schedule on two panels: A's upper rows with B's left columns,
and A's lower rows with B's right columns.  Each step is then four
products, one per quarter of C, in the order (0, 0), (0, 1), (1, 0),
(1, 1): the first needs only panel 0, so panel 1's skew moves under it.
C is assembled from its quarters once, after the last step.  Every
element of C is the same sum in the same step order, so the output is
allclose to one panel's, and bitwise the same where the backend's dot
blocks a quarter's product as it blocks the whole (the v5e does at the
2x2 benchmark cell's shape; the CPU does not at every shape).

Empty steps: the compute (and, via ``recv`` skipping, the broadcast)
of an empty step is elided, but ``shift`` still runs — later Cannon
steps need the rotated operands.

Names: ``execute_schedule`` opens ``jax.named_scope("dbcsr.skew")``
around the prologue, ``dbcsr.shift`` around every ``shift`` and
``recv`` and ``dbcsr.reduce`` around the epilogue, so that under
``jax.jit`` the collectives' ``op_name`` in the compiled program and
the profiler's trace says which phase sent them.  A scope is metadata only: the
ops, their order and the output are the same.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "Schedule",
    "RolledSpec",
    "DEFAULT_PIPELINE_DEPTH",
    "execute_schedule",
    "resolve_pipeline_depth",
    "schedule_step_meta",
    "skew_panels",
]

DEFAULT_PIPELINE_DEPTH = 2
# the named scopes of the schedule's communication phases
SKEW, SHIFT, REDUCE = "dbcsr.skew", "dbcsr.shift", "dbcsr.reduce"
# The least local rows of A and columns of B for which ``skew_panels``
# panels the prologue: the smallest square local block at which it was
# measured to pay.  Its gain is the half of the skew that moves under
# the first quarter's product, its cost the pass that assembles C; on a
# v5e 2x2 host, program alone, it saved 2.7%, 5.3% and 4.1% a call at
# local blocks of 16,896, 8,448 and 5,632, and smaller blocks were not
# measured
SKEW_PANEL_MIN = 5632
# the panels split at a multiple of the lane width, so each quarter of
# C stays aligned to the chip's (8, 128) tiles
SKEW_PANEL_ALIGN = 128


def _identity_prologue(a, b):
    return (a, b)


def _identity_recv(carry, t):
    return carry


def _identity_shift(carry, t):
    return carry


def _identity_epilogue(c):
    return c


@dataclasses.dataclass(frozen=True)
class RolledSpec:
    """Step-uniform shift for the ``fori_loop`` ablation form.

    Only schedules whose ``recv`` is the identity and whose ``shift``
    does not depend on the step index can roll (Cannon; not SUMMA,
    whose per-panel slice offsets are host constants).
    """

    shift: Callable  # carry -> carry (step-independent)


@dataclasses.dataclass(frozen=True)
class Schedule:
    """Host-side step plan consumed by ``execute_schedule``.

    The callables close over mesh-axis names and host constants only —
    building a Schedule traces nothing and is cheap, so callers may
    rebuild one purely to read its metadata (``multiply.py`` does, for
    the per-step comm/compute report).
    """

    algorithm: str
    n_steps: int
    prologue: Callable = _identity_prologue
    recv: Callable = _identity_recv
    shift: Callable = _identity_shift
    epilogue: Callable = _identity_epilogue
    empty_steps: frozenset = frozenset()
    rolled: Optional[RolledSpec] = None
    # -- observability metadata (host-side, optional) ------------------
    comm_op: str = ""                      # e.g. "ppermute(col,row)"
    prologue_comm_bytes: int = 0
    step_comm_bytes: Tuple[int, ...] = ()  # per-step estimate, len n_steps
    epilogue_comm_bytes: int = 0
    # the prologue moves bytes between chips, and prologue, shift and
    # recv each map slices of A's rows and B's columns to the same
    # slices of their results, with the carry the pair (A, B): so
    # ``execute_schedule`` may run the schedule on row/column panels
    # (``skew_panels``)
    row_col_panels: bool = False

    def replace(self, **kw) -> "Schedule":
        return dataclasses.replace(self, **kw)


def skew_panels(sched: Schedule, local_matmul: Callable, ml: int, nl: int,
                pipeline_depth: int) -> int:
    """How many row/column panels ``execute_schedule`` runs the schedule
    on: 2 at depth 2 where the schedule's prologue moves bytes and both
    the schedule and the local multiply can be sliced by A's rows and
    B's columns, no step is empty, and the local blocks hold at least
    ``SKEW_PANEL_MIN`` rows of A and columns of B; else 1, the one-panel
    program."""
    if (pipeline_depth >= 2 and sched.row_col_panels
            and not sched.empty_steps
            and getattr(local_matmul, "row_col_panels", False)
            and min(ml, nl) >= SKEW_PANEL_MIN):
        return 2
    return 1


def resolve_pipeline_depth(pipeline_depth: Optional[int],
                           double_buffer: Optional[bool] = None) -> int:
    """Fold the legacy ``double_buffer`` flag into the depth knob.

    ``pipeline_depth`` wins when given; otherwise ``double_buffer=True``
    (the historical default) maps to depth 2 and ``False`` to the rolled
    form (depth 0), preserving the pre-engine ablation semantics.
    """
    if pipeline_depth is not None:
        d = int(pipeline_depth)
        if d < 0:
            raise ValueError(f"pipeline_depth must be >= 0, got {d}")
        return min(d, 2)
    if double_buffer is None or double_buffer:
        return DEFAULT_PIPELINE_DEPTH
    return 0


def execute_schedule(
    sched: Schedule,
    a_blk: jax.Array,
    b_blk: jax.Array,
    *,
    local_matmul: Callable,
    out_dtype,
    pipeline_depth: int = DEFAULT_PIPELINE_DEPTH,
    accum_dtype=jnp.float32,
) -> jax.Array:
    """Run a schedule's step loop (inside shard_map) and return C.

    ``local_matmul`` may be *stepwise* (``local_matmul.stepwise``
    truthy): it is then called as ``local_matmul(a, b, step=t)`` and may
    return ``None`` for a step whose occupancy-mask product is empty on
    every rank (host-static and uniform across devices, so SPMD-safe to
    skip — the schedule's ``shift`` still runs, later steps need it).
    """
    stepwise = bool(getattr(local_matmul, "stepwise", False))
    empty = sched.empty_steps
    n = sched.n_steps
    depth = pipeline_depth
    if depth == 0 and (stepwise or empty or sched.rolled is None):
        # stepwise plans are distinct host constants a rolled body
        # cannot express; schedules without a rolled spec (per-step
        # recv offsets) cannot roll either
        depth = 1
    if skew_panels(sched, local_matmul, a_blk.shape[-2], b_blk.shape[-1],
                   depth) > 1:
        return _execute_panelled(sched, a_blk, b_blk, local_matmul,
                                 out_dtype, accum_dtype)

    with jax.named_scope(SKEW):
        carry = sched.prologue(a_blk, b_blk)
    # accumulator shape generalizes over leading batch dims: (m, n) for
    # one product, (G, m, n) for a fused product batch (the batched
    # multiply stacks G local operands as (G, ml, kl) x (G, kl, nl))
    c = jnp.zeros(a_blk.shape[:-1] + b_blk.shape[-1:], dtype=accum_dtype)

    if depth == 0:
        # Rolled (fori_loop): smaller HLO, no overlap.  Kept for the
        # ablation arm (bench_overlap measures the overlap win).
        rolled = sched.rolled

        def body(_, loop_carry):
            inner, c_c = loop_carry
            with jax.named_scope(SHIFT):
                a_c, b_c = sched.recv(inner, 0)
            c_c = c_c + local_matmul(a_c, b_c).astype(accum_dtype)
            with jax.named_scope(SHIFT):
                inner = rolled.shift(inner)
            return inner, c_c

        _, c = jax.lax.fori_loop(0, n, body, (carry, c))
        return _reduce(sched, c).astype(out_dtype)

    def compute(ops, t):
        a_t, b_t = ops
        part = (local_matmul(a_t, b_t, step=t) if stepwise
                else local_matmul(a_t, b_t))
        return part

    def advance(carry, t):
        # the communication feeding step t + 1: the carry's shift, then
        # that step's operands unless it is empty
        with jax.named_scope(SHIFT):
            nxt = sched.shift(carry, t)
            return nxt, (None if t + 1 in empty else sched.recv(nxt, t + 1))

    if 0 in empty:
        ops = None
    else:
        with jax.named_scope(SHIFT):
            ops = sched.recv(carry, 0)
    for t in range(n):
        nxt_carry = nxt_ops = None
        if depth >= 2 and t + 1 < n:
            # software double buffering: issue step t+1's communication
            # before step t's multiply so XLA overlaps the collective
            # with the compute
            nxt_carry, nxt_ops = advance(carry, t)
        if t not in empty:
            part = compute(ops, t)
            if part is not None:
                c = c + part.astype(accum_dtype)
        if t + 1 < n:
            if depth < 2:
                # serial: all communication strictly after the multiply
                nxt_carry, nxt_ops = advance(carry, t)
            carry, ops = nxt_carry, nxt_ops
    return _reduce(sched, c).astype(out_dtype)


def _execute_panelled(sched: Schedule, a_blk, b_blk, local_matmul,
                      out_dtype, accum_dtype) -> jax.Array:
    """``execute_schedule`` at depth 2 on two row/column panels (the
    module docstring): the prologue, shifts and receives run once per
    panel, each step runs the four quarters' products, and C is
    assembled from its quarters after the last step."""
    hm = _split(a_blk.shape[-2])
    hn = _split(b_blk.shape[-1])
    with jax.named_scope(SKEW):
        carries = [sched.prologue(a_blk[..., :hm, :], b_blk[..., :hn]),
                   sched.prologue(a_blk[..., hm:, :], b_blk[..., hn:])]

    with jax.named_scope(SHIFT):
        ops = [sched.recv(carry, 0) for carry in carries]
    quarters, last = {}, None
    for t in range(sched.n_steps):
        if t + 1 < sched.n_steps:
            # as at depth 2: step t+1's communication before step t
            with jax.named_scope(SHIFT):
                carries = [sched.shift(carry, t) for carry in carries]
                nxt_ops = [sched.recv(carry, t + 1) for carry in carries]
        for i, j in ((0, 0), (0, 1), (1, 0), (1, 1)):
            a_i, b_j = ops[i][0], ops[j][1]
            if last is not None:
                # each product waits for the one before it, so XLA keeps
                # this order (else it may run a product that needs panel
                # 1 first, with the skew exposed) and fuses each add
                # into its own product's dot
                quarters[last], a_i, b_j = jax.lax.optimization_barrier(
                    (quarters[last], a_i, b_j))
            part = local_matmul(a_i, b_j).astype(accum_dtype)
            quarters[i, j] = (part + quarters[i, j] if (i, j) in quarters
                              else part)
            last = (i, j)
        if t + 1 < sched.n_steps:
            ops = nxt_ops
    c = jnp.concatenate(
        [jnp.concatenate([quarters[i, 0], quarters[i, 1]], axis=-1)
         for i in (0, 1)], axis=-2)
    return _reduce(sched, c).astype(out_dtype)


def _split(n: int) -> int:
    """Where a panelled prologue splits ``n`` rows or columns: about
    half, at a multiple of ``SKEW_PANEL_ALIGN``."""
    return max(SKEW_PANEL_ALIGN,
               round(n / 2 / SKEW_PANEL_ALIGN) * SKEW_PANEL_ALIGN)


def _reduce(sched: Schedule, c: jax.Array) -> jax.Array:
    with jax.named_scope(REDUCE):
        return sched.epilogue(c)


def schedule_step_meta(sched: Schedule) -> dict:
    """Host-side summary of a schedule's communication structure —
    consumed by ``multiply.py`` to build the per-step comm/compute
    report attached to executed plans and by the telemetry layer
    (repro.obs) for dispatch-span comm-bytes attributes."""
    per_step = list(sched.step_comm_bytes) if sched.step_comm_bytes \
        else [0] * sched.n_steps
    return {
        "algorithm": sched.algorithm,
        "n_steps": sched.n_steps,
        "comm_op": sched.comm_op,
        "empty_steps": sorted(sched.empty_steps),
        "prologue_comm_bytes": int(sched.prologue_comm_bytes),
        "step_comm_bytes": [int(x) for x in per_step],
        "epilogue_comm_bytes": int(sched.epilogue_comm_bytes),
        "total_comm_bytes": int(sched.prologue_comm_bytes)
        + sum(int(x) for x in per_step)
        + int(sched.epilogue_comm_bytes),
    }
