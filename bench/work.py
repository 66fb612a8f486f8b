"""The work one multiply has to do, counted from the operands alone.

A kernel's roofline share divides the least time the chip could take for
this work by the time the trace shows.  The work is counted here, never
read from the program, so that it stays the same work whatever kernel
later implements it.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class Work:
    """Operations and least bytes of one multiply on the busiest chip."""

    flops: float
    bytes: float
    triples: Optional[int] = None   # retained block triples (blocked path)

    def roofline_s(self, peaks: dict) -> float:
        return max(self.flops / peaks["flops_per_s"],
                   self.bytes / peaks["hbm_bytes_per_s"])

    def bound(self, peaks: dict) -> str:
        compute = self.flops / peaks["flops_per_s"]
        return "compute" if compute >= self.bytes / peaks[
            "hbm_bytes_per_s"] else "memory"


def dense_work(m: int, k: int, n: int, itemsize: int, chips: int) -> Work:
    """``2 m k n`` operations split evenly over the chips; the least bytes
    are A and B read once and C written once, each chip its share."""
    return Work(flops=2.0 * m * k * n / chips,
                bytes=float(itemsize) * (m * k + k * n + m * n) / chips)


def retained(a_mask: np.ndarray, b_mask: np.ndarray, a_norms: np.ndarray,
             b_norms: np.ndarray, eps: float):
    """Block triples (i, k, j) with both blocks present and
    ``norm(A_ik) * norm(B_kj) >= eps``: their number, and the A blocks,
    B blocks and C blocks that at least one of them uses."""
    nbr, nbk = a_mask.shape
    nbc = b_mask.shape[1]
    a_used = np.zeros((nbr, nbk), bool)
    b_used = np.zeros((nbk, nbc), bool)
    c_used = np.zeros((nbr, nbc), bool)
    count = 0
    for k in range(nbk):
        rows = np.flatnonzero(a_mask[:, k])
        cols = np.flatnonzero(b_mask[k])
        keep = np.outer(a_norms[rows, k], b_norms[k, cols]) >= eps
        count += int(keep.sum())
        a_used[rows[keep.any(axis=1)], k] = True
        b_used[k, cols[keep.any(axis=0)]] = True
        r, c = np.nonzero(keep)
        c_used[rows[r], cols[c]] = True
    return count, a_used, b_used, c_used


def blocked_work(a_mask, b_mask, a_norms, b_norms, eps: float, bs: int,
                 itemsize: int) -> Tuple[Work, np.ndarray]:
    """``2 bs^3`` operations per retained triple; the least bytes are each
    used A and B block read once and each C block written once.  Also
    the product's support: the C blocks some retained triple writes."""
    count, a_used, b_used, c_used = retained(a_mask, b_mask, a_norms,
                                             b_norms, eps)
    blocks = int(a_used.sum() + b_used.sum() + c_used.sum())
    return Work(flops=2.0 * bs ** 3 * count,
                bytes=float(itemsize) * bs * bs * blocks,
                triples=count), c_used
