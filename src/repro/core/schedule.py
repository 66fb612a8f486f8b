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
                       overlap — changes.
  pipeline_depth = 0   rolled ``fori_loop`` form (smaller HLO, no
                       overlap) where the schedule provides a ``rolled``
                       spec; falls back to depth 1 otherwise.  Kept for
                       the HLO-size ablation (the legacy
                       ``double_buffer=False``).

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
]

DEFAULT_PIPELINE_DEPTH = 2
# the named scopes of the schedule's communication phases
SKEW, SHIFT, REDUCE = "dbcsr.skew", "dbcsr.shift", "dbcsr.reduce"


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

    def replace(self, **kw) -> "Schedule":
        return dataclasses.replace(self, **kw)


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
