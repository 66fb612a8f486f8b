"""Metric readers, one module per metric family, found by name: the
metric ``device_idle.blocked`` is read by ``device_idle.py``, and the part
after the first dot (``blocked``) is the path it reads.  Each module has
``read(r: Readings, path: str) -> float | None``; a reader that finds
nothing to read returns None, and the metric is left out of the line.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Callable, List, Optional

from bench.trace import Trace
from bench.work import Work


@dataclasses.dataclass
class Step:
    """One timed step on the host clock (seconds since the window
    opened): its inputs formed from ``t_start``, the library called at
    ``t_call``, returned at ``t_return``, its product ready at ``t_done``;
    the compile requests and persistent-cache hits the step made; and
    when it was due, which its latency counts from (a closed loop: the
    call)."""

    t_start: float
    t_call: float
    t_return: float
    t_done: float
    compiles: int
    cache_hits: int
    t_due: float = -1.0

    def __post_init__(self):
        if self.t_due < 0:
            self.t_due = self.t_call


@dataclasses.dataclass
class Readings:
    steps: List[Step]
    setup_s: float
    work: Work
    peaks: dict
    trace: Optional[Trace] = None
    log: Callable[[str], None] = print

    @property
    def window_s(self) -> float:
        """From the window's opening to the end of its last step."""
        return self.steps[-1].t_done if self.steps else 0.0


def load(name: str):
    family = name.split(".", 1)[0]
    return importlib.import_module(f"bench.metrics.{family}")


def read(name: str, r: Readings) -> Optional[float]:
    path = name.split(".", 1)[1] if "." in name else ""
    return load(name).read(r, path)


def per_step_s(r: Readings) -> Optional[float]:
    """Window seconds over the steps completed in it; the last step,
    which ends after ``--seconds``, counts whole."""
    return r.window_s / len(r.steps) if r.steps else None


def kernel_roofline(r: Readings, pattern, category) -> Optional[float]:
    """The kernel's share of its roofline, in %: the least time the chip
    could take for the window's work over the device time of the
    operations whose names match ``pattern`` and whose HLO categories
    match ``category``, the mean over the chips.  The names matched go to
    the log."""
    from bench import trace as tr

    if r.trace is None or not r.steps:
        return None
    per_chip, names = [], set()
    for d in range(len(r.trace.devices)):
        ops = tr.matching(r.trace, d, pattern, category)
        names.update(e.name for e in ops)
        per_chip.append(tr.length(tr.clip([(e.start, e.end) for e in ops],
                                          r.trace.window)))
    busy = sum(per_chip) / len(per_chip)
    r.log(f"  {pattern.pattern!r} in {category.pattern!r} matched "
          f"{len(names)} op names: "
          f"{sorted(names)[:20]}")
    if busy <= 0:
        return None
    return 100.0 * len(r.steps) * r.work.roofline_s(r.peaks) / busy
