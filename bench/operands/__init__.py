"""Operand generators, one module per kind, found by the name a
configuration gives under ``operand``.  Each module has
``make(config, seed, sharding) -> Operands`` and makes its arrays on the
device, in one jitted call that places them where they live, from
``seed`` alone."""
from __future__ import annotations

import dataclasses
import importlib
from typing import Optional

import numpy as np


@dataclasses.dataclass
class Operands:
    """What one product multiplies: ``a @ b``, or ``a @ a`` where ``b``
    is None.  Masks and norms are the generator's own (float64 norms),
    for the benchmark's count of the work; the program derives its own."""

    a: object
    b: Optional[object]
    a_mask: Optional[np.ndarray] = None
    b_mask: Optional[np.ndarray] = None
    a_norms: Optional[np.ndarray] = None
    b_norms: Optional[np.ndarray] = None


def seed32(seed: int, stream: int = 0) -> int:
    """A 31-bit seed from any whole ``seed`` (the benchmark's are larger
    than 32 bits), one per ``stream``."""
    state = np.random.SeedSequence([seed, stream]).generate_state(1)
    return int(state[0] & 0x7FFFFFFF)


def load(kind: str):
    return importlib.import_module(f"bench.operands.{kind}")
