"""The program's own spans in a traced run: the ``dbcsr.*`` profiler
annotations that ``repro.obs`` opens around each host phase of a
multiply (``dbcsr.multiply`` ⊃ ``dbcsr.plan``, ``dbcsr.stacks``,
``dbcsr.dispatch``, ``dbcsr.finish``), on the device trace's clock.

Names and intervals come from the trace's host events
(``Trace.host``).  The metadata that the root and the dispatch carry
(``lowerings``, ``compiles``, ``lower_s``, ``compile_s``, ``lowered``
...) is read again from the run's ``.xplane.pb``.  A program that opens no such
span gives nothing, and its readers return None.
"""
from __future__ import annotations

from pathlib import Path
from typing import Dict, List, Optional

from bench import trace as tr


def intervals(r, name: str) -> List[tr.Interval]:
    """The window's host intervals of the span ``name``, clipped to
    it."""
    return tr.clip([(e.start, e.end) for e in r.trace.host
                    if e.name == name], r.trace.window)


def seconds_per_step(r, name: str) -> Optional[float]:
    """Host seconds inside ``name`` in the window, over its steps."""
    if r.trace is None or not r.steps:
        return None
    inside = intervals(r, name)
    if not inside:
        return None
    return tr.length(inside) / len(r.steps)


def metadata(window: tr.Interval, name: str,
             log_dir: Optional[Path] = None) -> List[Dict[str, object]]:
    """The metadata of each host event ``name`` that starts in
    ``window``, from the newest ``.xplane.pb`` under ``log_dir`` (the
    harness's trace directory by default)."""
    from jax.profiler import ProfileData

    if log_dir is None:
        from bench.harness import TRACE_DIR as log_dir
    data = ProfileData.from_file(str(tr.find(log_dir)))
    lo, hi = window
    out = []
    for plane in data.planes:
        if plane.name != tr.HOST_PLANE:
            continue
        for line in plane.lines:
            out += [dict(e.stats) for e in line.events
                    if e.name == name and lo <= e.start_ns * 1e-9 <= hi]
    return out
