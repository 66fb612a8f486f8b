"""One ``dbcsr.multiply`` of a fresh operand per step, as a solver's inner
loop calls it.

Step ``t`` multiplies ``A * s_t`` by ``B``, or ``P * s_t`` by itself where
the operand generator gives one matrix, with the keyword arguments of the
configuration's ``multiply`` (the path it pins, ``filter_eps``).  The
scaling goes through ``DBCSRMatrix.scale``, which rescales cached block
norms exactly, so every value, norm and content fingerprint changes from
step to step while the block pattern and the retained triples stay.  No
``return_plan``, ``verify`` or telemetry is passed.

The work is counted from the operands: on the densified path
``2 m k n`` over the chips, on the block-sparse path the benchmark's own
retained triples.  The product is compared with the reference of the
same scaled operands (``bench/reference.py``); on the block-sparse path
its block mask is also compared with the benchmark's own retained
support.
"""
from __future__ import annotations

from typing import Iterable

import numpy as np

from bench import reference
from bench import work as work_mod


class ScaledMultiply:

    def __init__(self, config: dict, ops, mesh, chips: int):
        from repro.core import dbcsr

        self.dbcsr = dbcsr
        self.mesh = mesh
        self.kwargs = dict(config["multiply"])
        self.eps = self.kwargs.get("filter_eps")
        bs = config["block_size"]
        norms = self.eps is not None
        self.a_raw, self.b_raw = ops.a, ops.b   # b None: the product is a @ a
        self.A = dbcsr.create(ops.a, mesh=mesh, block_size=bs,
                              block_mask=ops.a_mask, compute_norms=norms)
        self.B = None if ops.b is None else dbcsr.create(
            ops.b, mesh=mesh, block_size=bs, block_mask=ops.b_mask,
            compute_norms=norms)
        right = ops.a if ops.b is None else ops.b
        (m, k), n = ops.a.shape, right.shape[1]
        itemsize = np.dtype(ops.a.dtype).itemsize
        self.support = None
        if self.kwargs["densify"]:
            self.work = work_mod.dense_work(m, k, n, itemsize, chips)
            return
        b_mask = ops.a_mask if ops.b is None else ops.b_mask
        b_norms = ops.a_norms if ops.b is None else ops.b_norms
        self.masks = (ops.a_mask, b_mask, ops.a_norms, b_norms)
        self.work, self.support = work_mod.blocked_work(
            *self.masks, self.eps or 0.0, bs, itemsize)

    # -- the timed path ----------------------------------------------------

    def form(self, s: float):
        a = self.A.scale(s)
        return a, (a if self.B is None else self.B)

    def call(self, x):
        return self.dbcsr.multiply(*x, mesh=self.mesh, **self.kwargs)

    def wait(self, c) -> None:
        c.data.block_until_ready()

    def describe(self, c) -> str:
        plan = getattr(c, "last_plan", None)
        if plan is None:
            return "not reported"
        return (f"algorithm={plan.algorithm} densify={plan.densify} "
                f"stack_tile={plan.stack_tile} align={plan.align}")

    def release(self) -> None:
        self.A = self.B = None

    # -- the check -----------------------------------------------------------

    def _operands(self, s: float):
        a = reference.scaled(self.a_raw, s)
        return a, (a if self.b_raw is None else self.b_raw)

    def compare(self, s: float, c) -> dict:
        out = reference.numbers(c.data, *self._operands(s))
        if self.support is not None:
            mask = (np.ones_like(self.support) if c.block_mask is None
                    else c.block_mask)
            out["mask_mismatch"] = int(np.count_nonzero(mask != self.support))
        return out

    def control(self, s: float) -> dict:
        a, b = self._operands(s)
        return reference.numbers(reference.control(a, b), a, b)

    def invariant(self, draws: Iterable[float]):
        """On the block-sparse path, the benchmark's count of retained
        triples is the same at every factor the window used."""
        if self.support is None:
            return True, "dense: every triple is retained"
        am, bm, an, bn = self.masks
        counts = {work_mod.retained(am, bm, an * s, bn * s,
                                    self.eps or 0.0)[0] for s in draws}
        return (counts <= {self.work.triples},
                f"retained triples at every factor: {sorted(counts)} "
                f"(set-up {self.work.triples})")


def build(config: dict, ops, mesh, chips: int) -> ScaledMultiply:
    return ScaledMultiply(config, ops, mesh, chips)
