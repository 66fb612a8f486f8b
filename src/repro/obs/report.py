"""Report rendering + the ``python -m repro.obs report`` CLI.

Consumes the JSONL logs a traced run leaves behind
(``events.jsonl`` + ``plan_outcomes.jsonl`` under ``--dir``) and
renders the two views the paper's evidence needs:

  breakdown   self time per phase (plan / stacks / dispatch / finish /
              matricize / verify / repair) across all recorded
              multiplies
  scoreboard  predicted-vs-actual planner cost per algorithm

``render_timeline`` prints one trace as an indented tree — recorded
spans, or the ``dbcsr.*`` spans of a profiler trace
(``export.profile_spans``).
"""
from __future__ import annotations

import argparse
import collections
import os
from typing import Dict, List, Optional, Sequence

from .telemetry import SpanRecord, EVENTS_LOG, PLAN_OUTCOMES_LOG
from .export import read_jsonl
from .scoreboard import planner_scoreboard, render_scoreboard

__all__ = ["category_breakdown", "render_breakdown", "render_timeline",
           "main"]

# the phases of a multiply ("matricize" = the tensor subsystem's
# unfold/refold phases under a contract root)
_PHASE_CATS = ("plan", "stacks", "dispatch", "finish", "matricize",
               "verify", "repair")


def category_breakdown(spans: Sequence[SpanRecord]) -> Dict[str, float]:
    """Self seconds per span category: each span's duration less the
    part its children cover, so nested phases are never counted twice
    (a repair's re-run dispatch counts under ``dispatch``, the checksum
    work around it under ``verify``).  ``total`` is the roots' time."""
    child_s: Dict[int, float] = collections.defaultdict(float)
    for s in spans:
        if s.dur >= 0 and s.parent_id is not None:
            child_s[s.parent_id] += s.dur
    out: Dict[str, float] = collections.defaultdict(float)
    for s in spans:
        if s.dur >= 0 and s.cat in _PHASE_CATS:
            out[s.cat] += s.dur - child_s[s.span_id]
    roots = [s for s in spans if s.parent_id is None and s.dur >= 0]
    out["total"] = sum(s.dur for s in roots)
    return dict(out)


def render_breakdown(spans: Sequence[SpanRecord]) -> str:
    bd = category_breakdown(spans)
    total = bd.get("total", 0.0)
    lines = ["where the time went (all recorded multiplies):"]
    for cat in _PHASE_CATS:
        if cat not in bd:
            continue
        frac = bd[cat] / total if total > 0 else 0.0
        lines.append(f"  {cat:<8} {bd[cat]*1e3:9.2f} ms  {frac:6.1%}")
    lines.append(f"  {'total':<8} {total*1e3:9.2f} ms")
    return "\n".join(lines)


def render_timeline(spans: Sequence[SpanRecord]) -> str:
    """One trace as an indented tree."""
    spans = [s for s in spans if s.dur >= 0]
    if not spans:
        return "(empty trace)"
    children: Dict[Optional[int], List[SpanRecord]] = \
        collections.defaultdict(list)
    for s in spans:
        children[s.parent_id].append(s)
    for v in children.values():
        v.sort(key=lambda s: (s.t0, s.span_id))
    lines: List[str] = []

    def _attrs(s: SpanRecord) -> str:
        keys = ("algorithm", "comm_bytes", "lowerings", "compiles",
                "cache_hits", "lower_s", "detected", "repaired")
        parts = [f"{k}={s.attrs[k]}" for k in keys
                 if s.attrs.get(k) is not None]
        return ("  [" + " ".join(parts) + "]") if parts else ""

    def _walk(parent_id: Optional[int], depth: int) -> None:
        for s in children.get(parent_id, []):
            lines.append(f"{'  ' * depth}{s.name:<20} "
                         f"{s.dur*1e3:9.3f} ms{_attrs(s)}")
            _walk(s.span_id, depth + 1)

    _walk(None, 0)
    return "\n".join(lines)


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.obs report",
        description="Render the per-phase breakdown and the "
                    "planner predicted-vs-actual scoreboard from a "
                    "traced run's JSONL logs.")
    ap.add_argument("--dir", default=os.path.join("artifacts", "obs"),
                    help="log directory passed to obs.enable(log_dir=...)")
    ap.add_argument("--timeline", action="store_true",
                    help="also print the last trace as a tree")
    args = ap.parse_args(argv)

    events = read_jsonl(os.path.join(args.dir, EVENTS_LOG))
    outcomes = read_jsonl(os.path.join(args.dir, PLAN_OUTCOMES_LOG))
    if not events and not outcomes:
        print(f"no telemetry logs under {args.dir!r} — run with "
              f"obs.enable(log_dir={args.dir!r}) first")
        return 1
    spans = [SpanRecord.from_dict(d) for d in events]
    n_traces = len({s.trace_id for s in spans})
    print(f"{len(spans)} spans over {n_traces} traces, "
          f"{len(outcomes)} plan outcomes from {args.dir}")
    if spans:
        print()
        print(render_breakdown(spans))
        if args.timeline:
            last_tid = max(s.trace_id for s in spans)
            print()
            print(render_timeline([s for s in spans
                                   if s.trace_id == last_tid]))
    if outcomes:
        print()
        print("planner scoreboard (predicted vs measured, signed "
              "rel err = (pred-meas)/meas):")
        print(render_scoreboard(planner_scoreboard(outcomes)))
    return 0
