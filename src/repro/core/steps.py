"""Which global blocks each rank holds at each step, and the plans the
blocked path reads from that.

A schedule's *step map* (``StepMap``) says, for every data-exchange
step t and every rank r, which block rows of A (and C), which blocks of
the contraction and which block columns of B (and C) rank r multiplies
at step t.  Ranks come in the flat order the rank-exact executor
indexes with ``axis_index``: Cannon ``i*pc + j``, Cannon-2.5D and the
stacked tall-skinny variants stack-major ``(p*pr + i)*pc + j``, SUMMA
and the flat tall-skinny variants ``i*pc + j``.  Each schedule module
builds its own map next to its schedule (``cannon_step_map``,
``summa_step_map``, ``ts_step_map``) and checks its divisibility there.

The views reduce any map over the global occupancy masks (and, under a
``filter_eps``, the block norms, zero where a block is absent):

  * ``union_view``: per step, the union over ranks — the one plan
    every rank can share under SPMD.  A map whose ranks share a step's
    K range (SUMMA, the all-gather SUMMA, ``ts_m``, ``ts_n``) keeps the
    factored ``a_mask``/``b_mask`` form: row and column ranks vary
    independently, so the union of per-rank products is the product of
    the unions, and for non-negative norms the max of the products is
    the product of the maxima.  Cannon and ``ts_k`` keep the
    ``(lr, lk, lc)`` pair tensor.
  * ``rank_view``: per step, each rank's own mask and norm slices.
  * ``empty_steps``: the steps where no rank retains a triple —
    under a ``filter_eps``, none whose norm product clears it.  The
    max norm product over ranks clears eps iff some rank retains, so
    this is the same set on either view, and the comm schedule never
    depends on which view planned the steps.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import numpy as np

__all__ = ["StepMap", "union_view", "rank_view", "masks_empty",
           "empty_steps"]


@dataclasses.dataclass(frozen=True)
class StepMap:
    """``index[t][r]`` is rank r's (A, B) block index at step t:
    ``(rows, ks)`` into A's block grid and ``(ks, cols)`` into B's, each
    range ``extent`` = (lr, lk, lc) blocks long.  ``pair``: the union
    keeps the pair tensor, because the ranks of a step do not share one
    K range."""
    index: Tuple[Tuple[Tuple[tuple, tuple], ...], ...]
    extent: Tuple[int, int, int]
    pair: bool

    @classmethod
    def of(cls, starts, extent, pair: bool) -> "StepMap":
        """From ``starts[t][r]`` = (first block row, first K block,
        first block column) of rank r at step t."""
        lr, lk, lc = extent
        index = tuple(
            tuple(((slice(r0, r0 + lr), slice(k0, k0 + lk)),
                   (slice(k0, k0 + lk), slice(c0, c0 + lc)))
                  for r0, k0, c0 in step)
            for step in starts)
        return cls(index, (lr, lk, lc), pair)


def _f32(norms):
    return None if norms is None else np.asarray(norms, dtype=np.float32)


def _fold(op, x: np.ndarray, idx: list) -> np.ndarray:
    """``op`` over ``x``'s blocks at each of ``idx``, in a new array (a
    view of ``x`` where ``idx`` has one entry); ``x`` is never written."""
    out = x[idx[0]]
    if len(idx) > 1:
        out = op(out, x[idx[1]])
        for ix in idx[2:]:
            op(out, x[ix], out=out)
    return out


def _distinct(indices) -> list:
    return list({(i[0].start, i[1].start): i for i in indices}.values())


def union_view(smap: StepMap, a_mask: np.ndarray, b_mask: np.ndarray,
               a_norms: Optional[np.ndarray] = None,
               b_norms: Optional[np.ndarray] = None) -> List[dict]:
    """Per step, the mask (and norm) kwargs of the stack plan every rank
    shares: the union of the ranks' presence and the max of their norm
    products (union-of-max, so ``filter_eps`` drops a triple only where
    it is below eps on every rank)."""
    an, bn = _f32(a_norms), _f32(b_norms)
    out = []
    for ranks in smap.index:
        if smap.pair:
            pm = np.zeros(smap.extent, dtype=bool)
            pn = None if an is None else np.zeros(smap.extent, np.float32)
            for ai, bi in ranks:
                ac = a_mask[ai]
                if not ac.any():
                    continue
                pm |= ac[:, :, None] & b_mask[bi][None, :, :]
                if pn is not None:
                    np.maximum(pn, an[ai][:, :, None] * bn[bi][None, :, :],
                               out=pn)
            kw = {"pair_mask": pm}
            if pn is not None:
                kw["pair_norms"] = pn
        else:
            a_idx = _distinct(ai for ai, _ in ranks)
            b_idx = _distinct(bi for _, bi in ranks)
            kw = {"a_mask": _fold(np.logical_or, a_mask, a_idx),
                  "b_mask": _fold(np.logical_or, b_mask, b_idx)}
            if an is not None:
                kw["a_norms"] = _fold(np.maximum, an, a_idx)
                kw["b_norms"] = _fold(np.maximum, bn, b_idx)
        out.append(kw)
    return out


def rank_view(smap: StepMap, a_mask: np.ndarray, b_mask: np.ndarray,
              a_norms: Optional[np.ndarray] = None,
              b_norms: Optional[np.ndarray] = None) -> List[List[dict]]:
    """``out[t][r]``: rank r's own ``a_mask``/``b_mask`` (and
    ``a_norms``/``b_norms``) slices at step t.  Eps filtering against
    them is DBCSR's exact local filter, not the union-of-max bound."""
    an, bn = _f32(a_norms), _f32(b_norms)
    steps = []
    for ranks in smap.index:
        kws = []
        for ai, bi in ranks:
            kw = {"a_mask": a_mask[ai], "b_mask": b_mask[bi]}
            if an is not None:
                kw["a_norms"] = an[ai]
                kw["b_norms"] = bn[bi]
            kws.append(kw)
        steps.append(kws)
    return steps


def masks_empty(mask_kwargs: dict) -> bool:
    """Host-static emptiness of one step's mask/norm kwargs: no
    mask-present triple — or, under a ``filter_eps`` with norms, no
    triple whose norm-product bound clears eps (norm filtering can
    empty a step whose binary masks are non-empty; the schedule driver
    then skips it exactly like a mask-empty step)."""
    eps = mask_kwargs.get("filter_eps")
    if "pair_mask" in mask_kwargs or "pair_norms" in mask_kwargs:
        pm = mask_kwargs.get("pair_mask")
        if pm is not None and not pm.any():
            return True
        pn = mask_kwargs.get("pair_norms")
        if eps and pn is not None:
            kept = pn if pm is None else np.where(pm, pn, 0.0)
            return not bool((kept.astype(np.float64) >= float(eps)).any())
        return False
    ua, ub = mask_kwargs["a_mask"], mask_kwargs["b_mask"]
    if not bool(np.any(ua.any(axis=0) & ub.any(axis=1))):
        return True
    un, vn = mask_kwargs.get("a_norms"), mask_kwargs.get("b_norms")
    if eps and un is not None and vn is not None:
        # max retained product per k: (max_i masked a) * (max_j masked b)
        ka = np.where(ua, un.astype(np.float64), 0.0).max(axis=0)
        kb = np.where(ub, vn.astype(np.float64), 0.0).max(axis=1)
        return not bool((ka * kb >= float(eps)).any())
    return False


def empty_steps(steps: Sequence[Sequence[dict]],
                filter_eps: Optional[float] = None) -> frozenset:
    """The steps at which every entry's kwargs (one per rank, one per
    fused group, or the one union shared by all) retain no triple."""
    return frozenset(
        t for t, entries in enumerate(steps)
        if all(masks_empty({**kw, "filter_eps": filter_eps})
               for kw in entries))
