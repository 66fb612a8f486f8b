"""Readers and writers: the JSONL logs of the telemetry layer, and the
``dbcsr.*`` spans of a profiler trace.

``profile_spans`` reads the spans back from the ``.xplane.pb`` a
``jax.profiler`` session writes, as :class:`SpanRecord` rows nested by
interval containment, so ``render_timeline`` shows a profiled multiply
the way it shows a recorded one; the annotations' metadata (the
dispatch's and the root's runtime counters) are the rows' attrs.
"""
from __future__ import annotations

import json
import os
from pathlib import Path
from typing import List, Sequence

from .telemetry import PROFILE_PREFIX, SpanRecord

__all__ = ["profile_spans", "write_jsonl", "read_jsonl"]


def profile_spans(log_dir: str) -> List[SpanRecord]:
    """The ``dbcsr.*`` host events of the newest profiler trace under
    ``log_dir``: ``t0``/``dur`` in seconds on the trace's clock, the
    innermost enclosing span on the same thread as parent."""
    from jax.profiler import ProfileData

    found = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    out: List[SpanRecord] = []
    for plane in ProfileData.from_file(str(found[-1])).planes:
        for line in plane.lines:
            events = sorted((e for e in line.events
                             if e.name.startswith(PROFILE_PREFIX)),
                            key=lambda e: (e.start_ns, -e.duration_ns))
            stack = []   # (end_ns, record) of the open enclosing spans
            for e in events:
                end_ns = e.start_ns + e.duration_ns
                while stack and stack[-1][0] < end_ns:
                    stack.pop()
                parent = stack[-1][1] if stack else None
                sid = len(out) + 1
                rec = SpanRecord(
                    name=e.name, cat="profile", span_id=sid,
                    parent_id=parent.span_id if parent else None,
                    trace_id=parent.trace_id if parent else sid,
                    t0=e.start_ns * 1e-9, dur=e.duration_ns * 1e-9,
                    attrs=dict(e.stats))
                out.append(rec)
                stack.append((end_ns, rec))
    return out


def write_jsonl(path: str, rows: Sequence[dict], *, mode: str = "a") -> str:
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, mode) as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    return path


def read_jsonl(path: str) -> List[dict]:
    rows: List[dict] = []
    if not os.path.exists(path):
        return rows
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                rows.append(json.loads(line))
    return rows
